"""Real-checkpoint serving evidence (VERDICT r4 #4).

Builds a GENUINE HuggingFace checkpoint on disk — a transformers
LlamaForCausalLM (seeded) saved with save_pretrained + a byte-level BPE
tokenizer.json trained with the `tokenizers` library — then serves it
through the FULL stack with the one-command launcher
(`python -m dynamo_tpu.run in=http:<port> out=native <dir>`:
HTTP -> preprocessor -> HF tokenizer -> NativeEngine -> incremental
detokenizer -> SSE), and asserts the streamed greedy completion is
IDENTICAL to `transformers` `generate()` on the same checkpoint. Records
TTFT and the JAX backend in the committed log.

No pretrained weights ship in this image (zero egress), so "real" here
means full checkpoint fidelity: the exact safetensors/config/tokenizer
file formats a user points the launcher at, loaded by the same code path
(`ModelDeploymentCard.from_hf_dir` + `load_params_from_hf`) that loads
Llama-3 checkpoints, with transformers as the independent oracle.
Reference analogue: launch/dynamo-run serving a hub checkpoint
(launch/dynamo-run/src/hub.rs).

Run: python tools/real_ckpt_e2e.py [--out LOG]
(JAX_PLATFORMS=cpu runs it on the CPU; on a TPU host the server child
holds the chip. This parent never initialises a JAX backend — it would
take the chip from the server — and logs the backend the server's own
READY line names.)
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from dynamo_tpu.utils.launch import read_device_tag  # noqa: E402  (no jax)

PROMPT = "The quick brown fox jumps over the lazy dog. "
MAX_NEW = 32
CORPUS = [
    "the quick brown fox jumps over the lazy dog",
    "pack my box with five dozen liquor jugs",
    "how vexingly quick daft zebras jump",
    "sphinx of black quartz judge my vow",
    "a journey of a thousand miles begins with a single step",
] * 20


def build_checkpoint(path: str) -> None:
    import torch
    from tokenizers import Tokenizer, models, pre_tokenizers, decoders, trainers
    from transformers import LlamaConfig, LlamaForCausalLM

    tok = Tokenizer(models.BPE())
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    tok.train_from_iterator(
        CORPUS, trainers.BpeTrainer(
            vocab_size=512, special_tokens=["</s>"],
            initial_alphabet=pre_tokenizers.ByteLevel.alphabet()))
    os.makedirs(path, exist_ok=True)
    tok.save(os.path.join(path, "tokenizer.json"))

    torch.manual_seed(7)
    cfg = LlamaConfig(
        vocab_size=tok.get_vocab_size(), hidden_size=256,
        intermediate_size=688, num_hidden_layers=4, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=2048,
        rope_theta=10000.0, rms_norm_eps=1e-5, tie_word_embeddings=False,
        eos_token_id=tok.token_to_id("</s>"), bos_token_id=None,
        attention_bias=False, torch_dtype="float32")
    model = LlamaForCausalLM(cfg)
    # overfit the tiny model on the corpus so greedy continuations are
    # recognizable English, not random bytes — the committed log then
    # shows REAL trained weights producing sensible text end-to-end
    ids = tok.encode(" ".join(CORPUS[:5]) + " ").ids * 8
    chunk = 64
    batch = torch.tensor([ids[i:i + chunk]
                          for i in range(0, len(ids) - chunk, chunk // 2)])
    opt = torch.optim.AdamW(model.parameters(), lr=3e-3)
    model.train()
    for step in range(120):
        opt.zero_grad()
        out = model(batch, labels=batch)
        out.loss.backward()
        opt.step()
        if out.loss.item() < 0.05:
            break
    print(f"[e2e] trained {step + 1} steps, loss {out.loss.item():.3f}",
          flush=True)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)


def oracle_continuation(path: str) -> str:
    import torch
    from tokenizers import Tokenizer
    from transformers import LlamaForCausalLM

    tok = Tokenizer.from_file(os.path.join(path, "tokenizer.json"))
    model = LlamaForCausalLM.from_pretrained(path).eval()
    ids = tok.encode(PROMPT).ids
    with torch.no_grad():
        out = model.generate(
            torch.tensor([ids]), do_sample=False, max_new_tokens=MAX_NEW,
            eos_token_id=tok.token_to_id("</s>"), pad_token_id=0)
    return tok.decode(out[0][len(ids):].tolist())


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def serve_and_query(path: str, extra_args: tuple = ()):
    """One-command launch, then a streamed /v1/completions request.
    Returns (text, ttft_ms, model_name, backend) — the backend is the
    platform the server's READY line names (utils/launch.device_tag)."""
    import threading

    port = free_port()
    env = {**os.environ, "PYTHONPATH": REPO}
    proc = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.run", f"in=http:{port}",
         "out=native", path, "--num-pages", "64", "--max-slots", "4",
         *extra_args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, cwd=REPO,
        env=env, text=True)
    model_name = None
    # a server that hangs producing no stdout would block readline()
    # forever; the timer turns that into EOF -> RuntimeError below
    watchdog = threading.Timer(600, proc.kill)
    watchdog.start()
    try:
        while True:
            line = proc.stdout.readline()
            if not line and proc.poll() is not None:
                raise RuntimeError("server exited (or hung past the "
                                   "watchdog) before READY")
            if line.startswith("READY"):
                model_name = line.split("model=")[1].split()[0]
                backend = read_device_tag(line)["platform"]
                break
        body = json.dumps({
            "model": model_name, "prompt": PROMPT, "stream": True,
            "max_tokens": MAX_NEW, "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        t0 = time.time()
        ttft_ms = None
        text = []
        with urllib.request.urlopen(req, timeout=300) as resp:
            for raw in resp:
                raw = raw.decode().strip()
                if not raw.startswith("data:"):
                    continue
                payload = raw[5:].strip()
                if payload == "[DONE]":
                    break
                chunk = json.loads(payload)
                piece = chunk["choices"][0].get("text", "")
                if piece and ttft_ms is None:
                    ttft_ms = (time.time() - t0) * 1000
                text.append(piece)
        return "".join(text), ttft_ms, model_name, backend
    finally:
        watchdog.cancel()
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "real_ckpt_e2e.log"))
    ap.add_argument("--dir", default="/tmp/real_ckpt_e2e_model")
    args = ap.parse_args()

    print(f"[e2e] building real HF checkpoint in {args.dir}", flush=True)
    build_checkpoint(args.dir)
    print("[e2e] transformers oracle generate()", flush=True)
    expect = oracle_continuation(args.dir)
    print(f"[e2e] oracle: {expect!r}", flush=True)
    print("[e2e] serving via `python -m dynamo_tpu.run in=http "
          "out=native` and streaming a completion", flush=True)
    got, ttft_ms, model_name, backend = serve_and_query(args.dir)
    print(f"[e2e] served: {got!r} (ttft "
          f"{'n/a' if ttft_ms is None else f'{ttft_ms:.1f} ms'})",
          flush=True)
    # speculative decoding on real weights: same stack with prompt-lookup
    # drafts must stream the IDENTICAL text (engine/spec.py exactness on a
    # genuine checkpoint, not just the random-weight unit tests)
    print("[e2e] re-serving with --spec-decode ngram", flush=True)
    spec_got, spec_ttft_ms, _, _ = serve_and_query(
        args.dir, ("--spec-decode", "ngram"))
    spec_ok = spec_got == got
    print(f"[e2e] spec-decode text "
          f"{'matches' if spec_ok else 'DIVERGES: ' + repr(spec_got)}",
          flush=True)
    ok = got == expect
    record = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": backend, "model": model_name, "prompt": PROMPT,
        "tokens": MAX_NEW,
        "ttft_ms": None if ttft_ms is None else round(ttft_ms, 1),
        "match": ok, "text": got,
        "oracle": expect if not ok else None,
        "spec_decode_match": spec_ok,
        "spec_ttft_ms": (None if spec_ttft_ms is None
                         else round(spec_ttft_ms, 1)),
    }
    with open(args.out, "a") as f:
        f.write(json.dumps(record) + "\n")
    # spec divergence on CPU/f32 is a real bug (both paths lower to the
    # same arithmetic); on TPU bf16 a near-tie argmax flip between the
    # verify and decode programs is the documented caveat (engine/spec.py)
    # — record it, but do not fail the run on it (code-review r5)
    spec_gates = spec_ok or backend == "tpu"
    print(f"[e2e] {'PASS' if ok and spec_gates else 'FAIL'}: full-stack "
          f"greedy text {'matches' if ok else 'DIVERGES from'} "
          f"transformers on backend={backend}; spec-decode pass "
          f"{'matches' if spec_ok else 'diverges (near-tie caveat on tpu; a BUG on cpu)'}; "
          f"log -> {args.out}", flush=True)
    sys.exit(0 if ok and spec_gates else 1)


if __name__ == "__main__":
    main()
