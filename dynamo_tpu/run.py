"""Single-command launcher: `python -m dynamo_tpu.run in=X out=Y [model]`.

Role of the reference's dynamo-run binary (reference:
launch/dynamo-run/src/opt.rs:23-133 `in={http|text|stdin|batch|endpoint|
none}` x `out={engines|echo|endpoint}`, lib.rs:54-260): one process that
wires an input frontend to an engine and runs it.

Inputs:
  in=http[:port]     OpenAI HTTP server (default port 8080)
  in=text            interactive chat REPL
  in=stdin           one prompt from stdin -> streamed completion -> exit
  in=batch:FILE      JSONL prompts -> JSONL completions on stdout
  in=endpoint:NS.COMP.EP  serve the engine as a control-plane endpoint
                     (worker mode; requires --control-host/--control-port)

Outputs (engines):
  out=native         in-process JAX engine (random-init weights unless the
                     model spec is an HF dir with weights)
  out=echo           deterministic token-echo engine (no hardware)

Model spec: a named architecture from the config registry ("tiny",
"llama3-1b", "llama3-8b", "mixtral-8x7b", ...) or a path to an HF-style
model directory (config.json + tokenizer.json).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import uuid

from dynamo_tpu.engine.config import EngineConfig, get_model_config
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.pipeline import LocalPipeline
from dynamo_tpu.llm.worker import (
    EchoTokenEngine, NativeEngineWorker, serve_llm_worker,
)
from dynamo_tpu.protocols.openai import ChatCompletionRequest
from dynamo_tpu.runtime.engine import Context
from dynamo_tpu.utils.launch import device_tag, enable_compile_cache

log = logging.getLogger("dynamo_tpu.run")


def build_card(model_spec: str) -> ModelDeploymentCard:
    if os.path.isdir(model_spec):
        return ModelDeploymentCard.from_hf_dir(model_spec)
    if model_spec.endswith(".gguf") and os.path.isfile(model_spec):
        # single-file serving, as the reference's `dynamo-run model.gguf`
        # (launch/dynamo-run/src/opt.rs GGUF detection): config,
        # tokenizer, chat template, and weights all from one file
        return ModelDeploymentCard.from_gguf(model_spec)
    return ModelDeploymentCard(name=model_spec, arch=model_spec,
                               tokenizer_kind="byte")


def chunk_buckets(spec: str, cap: int) -> tuple:
    """--prefill-buckets: the ladder of prefill chunk buckets, ascending;
    its largest holds --max-prefill-chunk (a chunk of the cap must have a
    bucket). Empty: EngineConfig's own."""
    if not spec:
        return EngineConfig.prefill_buckets
    ladder = tuple(sorted({int(b) for b in spec.split(",")}))
    if ladder[0] < 1 or ladder[-1] < cap:
        raise ValueError(
            f"--prefill-buckets {spec}: the largest bucket must hold "
            f"--max-prefill-chunk {cap}, and every bucket a token")
    return ladder


async def build_engine(out_spec: str, card: ModelDeploymentCard, args):
    if out_spec == "echo":
        return EchoTokenEngine(delay_s=args.echo_delay)
    if out_spec != "native":
        raise SystemExit(f"unknown out={out_spec!r}")
    import glob

    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.parallel.mesh import make_mesh
    enable_compile_cache()
    model_cfg = card.model_config()
    if args.quant:
        import dataclasses
        model_cfg = dataclasses.replace(model_cfg, quant=args.quant)
    params = None
    if card.model_path and card.model_path.endswith(".gguf"):
        from dynamo_tpu.llm.gguf import GGUFFile, load_params_from_gguf
        log.info("loading weights from %s", card.model_path)
        g = GGUFFile(card.model_path)
        try:
            # model_cfg already carries --quant, so the loader streams
            # per-projection int8 quantization during the load
            params = load_params_from_gguf(g, model_cfg)
        finally:
            g.close()
    elif card.model_path and glob.glob(
            os.path.join(card.model_path, "*.safetensors")):
        from dynamo_tpu.models.loader import load_params_from_hf
        log.info("loading weights from %s", card.model_path)
        params = load_params_from_hf(card.model_path, model_cfg)
    eng_cfg = EngineConfig(
        page_size=card.kv_page_size, num_pages=args.num_pages,
        max_slots=args.max_slots, max_prefill_chunk=args.max_prefill_chunk,
        prefill_buckets=chunk_buckets(args.prefill_buckets,
                                      args.max_prefill_chunk),
        mixed_token_budget=args.mixed_token_budget,
        max_prefill_batch=args.max_prefill_batch,
        decode_steps=args.decode_steps,
        max_model_len=min(card.context_length, model_cfg.max_model_len),
        tp=args.tp, sp=args.sp, host_pages=args.host_pages,
        spec_decode=args.spec_decode, spec_k=args.spec_k,
        spec_draft_model=args.spec_draft, kv_quant=args.kv_quant)
    n_mesh = args.tp * args.pp * args.ep * args.sp
    mesh = (make_mesh(tp=args.tp, pp=args.pp, ep=args.ep, sp=args.sp)
            if n_mesh > 1 else None)
    engine = NativeEngine(model_cfg, eng_cfg, mesh=mesh, params=params,
                          eos_token_ids=set(card.eos_token_ids))
    return await NativeEngineWorker(engine).start()


async def run_http(pipe: LocalPipeline, card, port: int) -> None:
    from dynamo_tpu.frontend.service import HttpService
    service = await HttpService(port=port).start()
    service.models.add(card.name, pipe, card.model_type)
    print(f"READY http=:{service.port} model={card.name}"
          f"{device_tag(pipe.engine)}", flush=True)
    await asyncio.Event().wait()


async def _stream_chat(pipe: LocalPipeline, card, prompt: str,
                       max_tokens: int, out=sys.stdout) -> None:
    req = ChatCompletionRequest(
        model=card.name, stream=True, max_tokens=max_tokens,
        messages=[{"role": "user", "content": prompt}])
    ctx = Context(uuid.uuid4().hex)
    async for chunk in pipe.generate_chat(req, ctx):
        for choice in chunk.choices:
            if choice.delta.content:
                out.write(choice.delta.content)
                out.flush()
    out.write("\n")


async def run_text(pipe: LocalPipeline, card, max_tokens: int) -> None:
    print(f"model={card.name}; empty line to exit", flush=True)
    loop = asyncio.get_running_loop()
    while True:
        line = await loop.run_in_executor(None, lambda: input("> "))
        if not line.strip():
            return
        await _stream_chat(pipe, card, line, max_tokens)


async def run_stdin(pipe: LocalPipeline, card, max_tokens: int) -> None:
    prompt = sys.stdin.read().strip()
    await _stream_chat(pipe, card, prompt, max_tokens)


async def run_batch(pipe: LocalPipeline, card, path: str,
                    max_tokens: int) -> None:
    """JSONL in ({"prompt": ...}), JSONL out ({"prompt", "text"})."""
    with open(path) as f:
        prompts = [json.loads(line)["prompt"] for line in f if line.strip()]

    async def one(prompt):
        from dynamo_tpu.protocols.delta import aggregate_chat_chunks
        req = ChatCompletionRequest(
            model=card.name, stream=False, max_tokens=max_tokens,
            messages=[{"role": "user", "content": prompt}])
        chunks = [c async for c in pipe.generate_chat(req, Context())]
        agg = aggregate_chat_chunks(chunks)
        return {"prompt": prompt,
                "text": agg.choices[0].message.content,
                "finish_reason": agg.choices[0].finish_reason}

    results = await asyncio.gather(*(one(p) for p in prompts))
    for r in results:
        print(json.dumps(r), flush=True)


async def run_endpoint(engine, card, spec: str, args) -> None:
    from dynamo_tpu.frontend.discovery import register_model
    from dynamo_tpu.runtime.distributed import DistributedRuntime
    try:
        ns, comp, ep = spec.split(".", 2)
    except ValueError:
        raise SystemExit("in=endpoint needs NS.COMPONENT.ENDPOINT")
    runtime = await DistributedRuntime.connect(
        args.control_host, args.control_port)
    served = await serve_llm_worker(runtime, ns, comp, engine, endpoint=ep,
                                    card=card)
    await register_model(runtime.kv, card.name, ns, comp, card, endpoint=ep,
                         model_type=card.model_type)
    from dynamo_tpu.llm.worker import install_graceful_drain
    install_graceful_drain(runtime, served)
    print(f"READY endpoint={spec} model={card.name}"
          f"{device_tag(engine)}", flush=True)
    await runtime.shutdown_event.wait()


async def amain() -> None:
    p = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("io", nargs="+",
                   help="in=... out=... [model] (order-free key=value)")
    p.add_argument("--max-tokens", type=int, default=256)
    p.add_argument("--num-pages", type=int, default=512)
    p.add_argument("--max-slots", type=int, default=8)
    p.add_argument("--max-prefill-chunk", type=int, default=512)
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated prefill chunk buckets, one program "
                        "set each (default: EngineConfig's 16..512); a "
                        "deployment whose prompts are short drops the "
                        "buckets it never fills")
    p.add_argument("--mixed-token-budget", type=int,
                   default=EngineConfig.mixed_token_budget,
                   help="device compute tokens of one fused prefill+decode "
                        "step, every row charged its chunk bucket: sets "
                        "the prefill chunk that rides beside the decode "
                        "rows (with 64 slots the default leaves 16 tokens "
                        "a step, and admission bounds the engine)")
    p.add_argument("--max-prefill-batch", type=int,
                   default=EngineConfig.max_prefill_batch,
                   help="prompts whose chunks may share one step; each "
                        "holds a state slot of a recurrent-state model "
                        "beyond --max-slots while it prefills")
    p.add_argument("--decode-steps", type=int,
                   default=EngineConfig.decode_steps,
                   help="device steps of a full decode window: a stream "
                        "sees no token for that many steps, then all of "
                        "them at once")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel stages (layer-sharded params + "
                        "cache, microbatched GPipe decode windows; "
                        "models/pp.py)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel shards for MoE configs "
                        "(ops/moe.py O(E/ep) dispatch)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence-parallel shards for ring-attention "
                        "prefill (ops/ring_attention.py)")
    p.add_argument("--quant", default="", choices=("", "int8"),
                   help="weight-only quantization: int8 halves weight HBM "
                        "and decode weight reads (ops/quant.py)")
    p.add_argument("--kv-quant", default="", choices=("", "int8"),
                   help="KV-cache page quantization: int8 pages + per-row "
                        "scales end-to-end (capture -> paged read -> "
                        "offload tiers -> disagg transfer), ~1.9x HBM "
                        "page capacity and ~2x fewer transfer bytes "
                        "(ops/kv_quant.py; parity-gated)")
    p.add_argument("--host-pages", type=int, default=0)
    p.add_argument("--spec-decode", default="",
                   choices=("", "ngram", "draft"),
                   help="speculative decoding: 'ngram' verifies "
                        "prompt-lookup drafts, 'draft' verifies a small "
                        "draft model's tokens, one target forward per "
                        "window (greedy plans; exact output)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="draft tokens verified per forward with "
                        "--spec-decode")
    p.add_argument("--spec-draft", default="",
                   help="draft model for --spec-decode draft: a registry "
                        "name or an HF checkpoint dir (vocab must match "
                        "the served model)")
    p.add_argument("--echo-delay", type=float, default=0.0)
    p.add_argument("--control-host", default="127.0.0.1")
    p.add_argument("--control-port", type=int, default=5550)
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator (host:port) when this "
                        "engine spans processes/hosts; see DYN_COORD_ADDR")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args()
    from dynamo_tpu.utils.logconfig import configure_logging
    configure_logging("debug" if args.verbose else "info")
    from dynamo_tpu.parallel.bootstrap import bootstrap_distributed
    bootstrap_distributed(args.coordinator, args.num_processes,
                          args.process_id)

    in_spec, out_spec, model_spec = "text", "echo", "tiny"
    for tok in args.io:
        if tok.startswith("in="):
            in_spec = tok[3:]
        elif tok.startswith("out="):
            out_spec = tok[4:]
        else:
            model_spec = tok

    card = build_card(model_spec)
    engine = await build_engine(out_spec, card, args)

    if in_spec.startswith("endpoint:"):
        await run_endpoint(engine, card, in_spec[len("endpoint:"):], args)
        return
    pipe = LocalPipeline(card, engine)
    if in_spec == "http" or (in_spec.startswith("http:")
                             and in_spec[5:].isdigit()):
        port = int(in_spec[5:]) if in_spec != "http" else 8080
        await run_http(pipe, card, port)
    elif in_spec == "text":
        await run_text(pipe, card, args.max_tokens)
    elif in_spec == "stdin":
        await run_stdin(pipe, card, args.max_tokens)
    elif in_spec.startswith("batch:"):
        await run_batch(pipe, card, in_spec[len("batch:"):], args.max_tokens)
    elif in_spec == "none":
        print("READY (in=none; engine built, exiting)"
              f"{device_tag(engine)}", flush=True)
    else:
        raise SystemExit(f"unknown in={in_spec!r}")


def main() -> None:
    try:
        asyncio.run(amain())
    except (KeyboardInterrupt, EOFError):
        pass


if __name__ == "__main__":
    main()
