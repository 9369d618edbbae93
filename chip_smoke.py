#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that dynamo-tpu still starts on the chip.

Drives the serving path once, through the entry points a user is told to
use, at the full width of a registry model with seeded random weights, and
checks what comes out. Run it from the root of a checkout:

    python3 chip_smoke.py                 # on a machine with a TPU
    python3 chip_smoke.py --rehearsal     # CPU dry run of the same command

This process never imports JAX: a chip belongs to one process at a time,
so every phase runs in child processes, one phase after another, and the
chip is free between phases. Each phase prints one JSON line naming the
platform, device_kind and device count reported by the process that held
the chip; the last line is the verdict for the run.

Phases:
  aggregated (1 chip)   `python -m dynamo_tpu.run in=http:<port> out=native
                        llama3-1b`, two identical rounds of chat requests
                        over the real socket (streamed and not, several in
                        flight, four prefill buckets, greedy / sampled /
                        logprobs). Every request must answer 200 with
                        finish_reason "length" and exactly max_tokens
                        tokens; /metrics must show the tokens and no
                        errors; the second round must dispatch no program
                        the first did not (llm_engine_recompiles).
  kernel (1 chip)       the ragged Pallas decode kernel, compiled (never
                        interpreted), against the XLA gather path at the
                        registry's two head geometries, bf16 and int8.
  disagg (>= 2 chips)   `python -m dynamo_tpu.sdk.serve
                        examples.disagg.graph:Frontend -f <config>
                        --start-control-plane --tpu-chips 2`: prefill and
                        decode engines each in their own process on their
                        own chip; a long prompt must come back through
                        remote prefill + KV transfer.
  tp4 (>= 4 chips)      `python -m dynamo_tpu.run in=http out=native
                        llama3-8b --tp 4`; the weights must be spread over
                        the four chips, never staged on one.

A phase that does not apply (too few chips) prints "skipped" with the
reason; it is never printed as passed. Any failed phase makes the exit
code nonzero. With no TPU (and no --rehearsal) the script exits nonzero
and prints nothing on stdout. --rehearsal runs `aggregated` and `disagg`
on the `tiny` model on the CPU so the command can be debugged without a
chip: every line it prints says "rehearsal" and "cpu", and its last line
says "ok": false — it is not a result for the chip.

Set-up times in the output (seconds to READY, first-request and
warm-request latency, compile-cache entries) are set-up times, not
performance numbers.
"""
from __future__ import annotations

import argparse
import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
PHASES = ("aggregated", "kernel", "disagg", "tp4")
CHIPS_NEEDED = {"aggregated": 1, "kernel": 1, "disagg": 2, "tp4": 4}
REHEARSAL_PHASES = ("aggregated", "disagg")

# every child this process started and has not reaped; killed on any exit
_CHILDREN: list = []


def log(*a) -> None:
    print("[chip_smoke]", *a, file=sys.stderr, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# child processes
# ---------------------------------------------------------------------------

class Child:
    """A child process in its own session: stdout collected line by line
    (READY detection), stderr to a log file, killed as a group."""

    def __init__(self, tag: str, argv: list, env: dict):
        os.makedirs(LOG_DIR, exist_ok=True)
        self.tag = tag
        self.lines: list = []
        self._cond = threading.Condition()
        self._err = open(os.path.join(LOG_DIR, f"{tag}.stderr.log"), "w")
        self.proc = subprocess.Popen(
            argv, stdout=subprocess.PIPE, stderr=self._err, text=True,
            cwd=HERE, env=env, start_new_session=True)
        _CHILDREN.append(self)
        self._reader = threading.Thread(target=self._pump, daemon=True)
        self._reader.start()

    def _pump(self) -> None:
        with open(os.path.join(LOG_DIR, f"{self.tag}.stdout.log"), "w") as f:
            for line in self.proc.stdout:
                f.write(line)
                f.flush()
                with self._cond:
                    self.lines.append(line.rstrip("\n"))
                    self._cond.notify_all()
        with self._cond:
            self.lines.append(None)   # EOF marker
            self._cond.notify_all()

    def wait_line(self, pred, timeout: float):
        """First stdout line satisfying pred, or raise on EOF / timeout."""
        deadline = time.monotonic() + timeout
        seen = 0
        with self._cond:
            while True:
                while seen < len(self.lines):
                    line = self.lines[seen]
                    seen += 1
                    if line is None:
                        raise RuntimeError(
                            f"{self.tag} exited (rc={self.proc.poll()}) "
                            f"before the expected line; see "
                            f"{LOG_DIR}/{self.tag}.stderr.log")
                    if pred(line):
                        return line
                left = deadline - time.monotonic()
                if left <= 0:
                    raise TimeoutError(
                        f"{self.tag}: no expected line in {timeout:.0f}s")
                self._cond.wait(left)

    def stop(self) -> None:
        if self.proc.poll() is None:
            # SIGINT is the launchers' clean exit (sdk.serve then stops
            # its services; an engine process releases its chip)
            try:
                os.killpg(self.proc.pid, signal.SIGINT)
            except (ProcessLookupError, PermissionError):
                pass
            try:
                self.proc.wait(20)
            except subprocess.TimeoutExpired:
                pass
        # the session may hold grandchildren (sdk.serve's services)
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(15)
        except subprocess.TimeoutExpired:
            log(f"{self.tag} did not die")
        self._reader.join(5)
        self._err.close()
        if self in _CHILDREN:
            _CHILDREN.remove(self)


def stop_all() -> None:
    for c in list(_CHILDREN):
        c.stop()


def child_env(rehearsal: bool) -> dict:
    env = dict(os.environ, PYTHONPATH=HERE + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def run_json_child(tag: str, mode: str, env: dict, timeout: float) -> dict:
    """Run `chip_smoke.py --child <mode>` to completion; its last stdout
    line is one JSON object."""
    child = Child(tag, [sys.executable, os.path.abspath(__file__),
                        "--child", mode], env)
    try:
        try:
            child.proc.wait(timeout)
        except subprocess.TimeoutExpired:
            raise TimeoutError(f"{tag} still running after {timeout:.0f}s")
        child._reader.join(10)
        out = [ln for ln in child.lines if ln]
        if child.proc.returncode != 0 or not out:
            raise RuntimeError(
                f"{tag} failed (rc={child.proc.returncode}); see "
                f"{LOG_DIR}/{tag}.stderr.log")
        return json.loads(out[-1])
    finally:
        child.stop()


def ready_device(line: str) -> dict:
    from dynamo_tpu.utils.launch import read_device_tag   # jax-free module
    return read_device_tag(line)


# ---------------------------------------------------------------------------
# HTTP client (stdlib, one connection per request)
# ---------------------------------------------------------------------------

def http_get(port: int, path: str, timeout: float = 30.0) -> str:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"GET {path} -> {resp.status}")
        return body
    finally:
        conn.close()


def metric_sum(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus metric's samples whose labels include `labels`;
    0.0 when absent."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name) or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        metric, _, lab = head.partition("{")
        if metric != name:
            continue
        if all(f'{k}="{v}"' in lab for k, v in labels.items()):
            total += float(value)
    return total


class ChatResult:
    def __init__(self):
        self.status = None
        self.finish_reasons: list = []
        self.completion_tokens = None
        self.logprobs: list = []       # [(logprob, [top logprobs])]
        self.latency_s = None
        self.error = None


def chat(port: int, body: dict, first_token: threading.Event = None,
         timeout: float = 600.0) -> ChatResult:
    """POST /v1/chat/completions; handles the unary and the SSE answer.
    `first_token` is set as soon as the first choice delta arrives (or
    when the request ends), which is what lets the caller stage arrivals."""
    res = ChatResult()
    t0 = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/v1/chat/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        res.status = resp.status
        if resp.status != 200:
            res.error = resp.read().decode(errors="replace")[:300]
            return res

        def take(choices):
            for ch in choices:
                if ch.get("finish_reason"):
                    res.finish_reasons.append(ch["finish_reason"])
                for ent in ((ch.get("logprobs") or {}).get("content") or ()):
                    res.logprobs.append(
                        (ent["logprob"],
                         [t["logprob"] for t in ent["top_logprobs"]]))

        if not body.get("stream"):
            out = json.loads(resp.read())
            take(out["choices"])
            res.completion_tokens = out["usage"]["completion_tokens"]
            return res
        while True:
            line = resp.readline()
            if not line:
                break
            line = line.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                break
            chunk = json.loads(data)
            take(chunk.get("choices") or ())
            if first_token is not None and chunk.get("choices"):
                first_token.set()
            if chunk.get("usage"):
                res.completion_tokens = chunk["usage"]["completion_tokens"]
        return res
    except Exception as e:   # recorded: the caller fails the phase on it
        res.error = f"{type(e).__name__}: {e}"
        return res
    finally:
        res.latency_s = round(time.monotonic() - t0, 3)
        if first_token is not None:
            first_token.set()
        conn.close()


# ---------------------------------------------------------------------------
# the request plan
# ---------------------------------------------------------------------------
# Prompt lengths are in TOKENS. Registry models serve with the byte
# tokenizer and the default chat template "<|user|>{content}</s><|assistant|>",
# so a prompt is len(content) + 25 tokens. Every request's content starts
# with its own round/index tag, so no two prompts share a full KV page and
# the prefix cache never changes a request's shape: the second round is
# identical to the first in every shape the engine buckets on.
#
# The plan is built so that which programs the engine dispatches does not
# depend on timing (the second-round check would otherwise be a coin toss):
# requests run one at a time, or arrive in a causal chain (each sent when
# the previous one streamed its first token, so it joins as a mixed
# prefill+decode step with a known number of decode rows), or as one n=4
# request (four sequences admitted together). The chained requests share
# one shape whose KV stays inside one page bucket (385..512 tokens), and
# two single requests first walk that shape's window tail through the
# 2-step and 1-step rungs, so every window program the chain can reach is
# already dispatched whichever request happens to finish first.

TEMPLATE_TOKENS = 25
GREEDY = {"temperature": 0.0}
SAMPLED = {"temperature": 0.8, "top_p": 1.0}        # the API's default
SAMPLED_TOP_P = {"temperature": 0.8, "top_p": 0.9}  # same window program
CHAIN_LEN = 390


def make_body(model: str, tag: str, n_tokens: int, max_tokens: int,
              stream: bool, sampling: dict, seed: int, **extra) -> dict:
    content = (f"{tag} " + "the quick brown fox jumps over the lazy dog " * 64
               )[:n_tokens - TEMPLATE_TOKENS]
    body = {"model": model, "max_tokens": max_tokens, "stream": stream,
            "seed": seed, "ext": {"ignore_eos": True},
            "messages": [{"role": "user", "content": content}],
            **sampling, **extra}
    if stream:
        body["stream_options"] = {"include_usage": True}
    return body


def serving_plan(model: str, rnd: int, full: bool) -> list:
    """One round: a list of stages; a stage is ("serial" | "chain", [body]).
    `full` is the aggregated plan; the tp4 phase runs the short one."""
    def body(i, *a, **kw):
        return make_body(model, f"r{rnd}q{i}", *a, seed=1000 * rnd + i, **kw)

    if not full:
        return [
            ("serial", [body(0, 40, 24, False, GREEDY),
                        body(1, 200, 24, False, GREEDY),
                        body(2, 100, 24, True, SAMPLED),
                        body(3, 100, 16, False, GREEDY, logprobs=True,
                             top_logprobs=3)]),
            ("serial", [body(4, CHAIN_LEN, 43, True, SAMPLED),
                        body(5, CHAIN_LEN, 42, True, SAMPLED)]),
            ("chain", [body(6, CHAIN_LEN, 64, True, SAMPLED),
                       body(7, CHAIN_LEN, 64, True, SAMPLED)]),
        ]
    return [
        # four prefill buckets (64 / 128 / 256 / 512), one request at a time
        ("serial", [body(0, 40, 40, False, GREEDY),
                    body(1, 100, 40, False, GREEDY),
                    body(2, 200, 40, True, GREEDY),
                    body(3, 400, 40, False, GREEDY)]),
        ("serial", [body(4, 100, 24, False, GREEDY, logprobs=True,
                         top_logprobs=3),
                    body(5, 100, 40, True, SAMPLED_TOP_P),
                    body(6, 100, 40, True, SAMPLED)]),
        # window tail of the chain shape: 42 = 5*8+2 and 41 = 5*8+1 tokens
        # after the prefill token end on the 2-step and the 1-step rung
        ("serial", [body(7, CHAIN_LEN, 43, True, SAMPLED),
                    body(8, CHAIN_LEN, 42, True, SAMPLED)]),
        # three requests in flight at once, joined as mixed steps
        ("chain", [body(9, CHAIN_LEN, 120, True, SAMPLED),
                   body(10, CHAIN_LEN, 120, True, SAMPLED),
                   body(11, CHAIN_LEN, 120, True, SAMPLED)]),
        # four sequences admitted together
        ("serial", [body(12, 100, 40, False, SAMPLED, n=4)]),
    ]


def run_round(port: int, plan: list) -> list:
    """Run one round; returns [(body, ChatResult)] in plan order."""
    done = []
    for kind, bodies in plan:
        if kind == "serial":
            for b in bodies:
                done.append((b, chat(port, b)))
            continue
        threads, slots = [], []
        for b in bodies:
            started = threading.Event()
            slot = []
            t = threading.Thread(
                target=lambda b=b, s=slot, e=started:
                s.append(chat(port, b, first_token=e)))
            t.start()
            threads.append(t)
            slots.append((b, slot))
            started.wait(600)   # next arrival joins a running decode
        for t in threads:
            t.join(900)
        for b, slot in slots:
            done.append((b, slot[0] if slot else ChatResult()))
    return done


def check_round(done: list) -> list:
    """Every way a round's answers can be wrong, as strings."""
    bad = []
    for b, r in done:
        tag = b["messages"][0]["content"].split(" ", 1)[0]
        n = b.get("n", 1)
        want = b["max_tokens"] * n
        if r.error or r.status != 200:
            bad.append(f"{tag}: status={r.status} error={r.error}")
            continue
        if r.finish_reasons != ["length"] * n:
            bad.append(f"{tag}: finish_reasons={r.finish_reasons}")
        if r.completion_tokens != want:
            bad.append(f"{tag}: completion_tokens={r.completion_tokens} "
                       f"want {want}")
        if b.get("logprobs"):
            if len(r.logprobs) != want:
                bad.append(f"{tag}: {len(r.logprobs)} logprob entries, "
                           f"want {want}")
            for lp, tops in r.logprobs:
                vals = [lp] + tops
                # finite and a log-probability; greedy: the sampled token
                # is the argmax, so its logprob is the top-1 alternative's
                # (one rounding apart at most) and the alternatives descend
                if not all(isinstance(v, float) and v == v
                           and -1e30 < v <= 1e-3 for v in vals):
                    bad.append(f"{tag}: non-finite / positive logprob "
                               f"{vals}")
                    break
                if len(tops) != b["top_logprobs"] \
                        or tops != sorted(tops, reverse=True) \
                        or abs(lp - tops[0]) > 1e-3:
                    bad.append(f"{tag}: greedy logprob {lp} vs top "
                               f"alternatives {tops}")
                    break
    return bad


def serve_and_check(tag: str, model: str, flags: list, rehearsal: bool,
                    full: bool, ready_timeout: float) -> dict:
    """Start `python -m dynamo_tpu.run in=http:<port> out=native <model>`,
    run two identical rounds, check the answers, /metrics and the second
    round's program count."""
    port = free_port()
    argv = [sys.executable, "-m", "dynamo_tpu.run", f"in=http:{port}",
            "out=native", model, *flags]
    t0 = time.monotonic()
    server = Child(tag, argv, child_env(rehearsal))
    try:
        line = server.wait_line(lambda ln: ln.startswith("READY"),
                                ready_timeout)
        out = {"device": ready_device(line),
               "seconds_to_ready": round(time.monotonic() - t0, 1)}
        bad, recompiles, latencies = [], [], []
        sent = seqs = prompt_toks = tokens = 0
        for rnd in (1, 2):
            before = metric_sum(http_get(port, "/metrics"),
                                "llm_engine_recompiles")
            done = run_round(port, serving_plan(model, rnd, full))
            bad += [f"round {rnd}: {m}" for m in check_round(done)]
            for b, _ in done:
                n = b.get("n", 1)
                sent += 1
                seqs += n
                tokens += b["max_tokens"] * n
                prompt_toks += n * (len(b["messages"][0]["content"])
                                    + TEMPLATE_TOKENS)
            latencies.append(done[0][1].latency_s)
            recompiles.append(int(metric_sum(
                http_get(port, "/metrics"), "llm_engine_recompiles")
                - before))
        # /metrics: every request a success, every token frame counted by
        # the frontend (one TTFT sample per sequence, one ITL sample per
        # later token), and the engine ledger's committed tokens cover
        # them (prompt tokens + every token after each sequence's first)
        m = http_get(port, "/metrics")
        seen = {
            "requests": metric_sum(m, "llm_http_service_requests_total"),
            "successes": metric_sum(m, "llm_http_service_requests_total",
                                    status="success"),
            "ttft_samples": metric_sum(m, "llm_ttft_seconds_count"),
            "itl_samples": metric_sum(m, "llm_itl_seconds_count"),
        }
        want = {"requests": sent, "successes": sent, "ttft_samples": seqs,
                "itl_samples": tokens - seqs}
        if seen != want:
            bad.append(f"/metrics shows {seen}, want {want}")
        useful = metric_sum(m, "llm_engine_tokens_useful")
        if useful < prompt_toks + tokens - seqs:
            bad.append(f"/metrics: llm_engine_tokens_useful={useful} < "
                       f"{prompt_toks} prompt + {tokens - seqs} decode")
        if recompiles[1] != 0:
            bad.append(f"second identical round first-dispatched "
                       f"{recompiles[1]} programs (first: {recompiles[0]})")
        if server.proc.poll() is not None:
            bad.append(f"server exited rc={server.proc.returncode}")
        out.update(requests=sent, sequences=seqs, completion_tokens=tokens,
                   programs_first_dispatched=recompiles,
                   first_request_s=latencies[0],
                   warm_request_s=latencies[1], failures=bad)
        return out
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# phases (parent side)
# ---------------------------------------------------------------------------

def phase_aggregated(rehearsal: bool) -> dict:
    before = cache_entries()
    out = serve_and_check("aggregated", "tiny" if rehearsal else "llama3-1b",
                          [], rehearsal, full=True, ready_timeout=600)
    out["compile_cache_entries"] = [before, cache_entries()]
    return out


def phase_kernel(rehearsal: bool) -> dict:
    out = run_json_child("kernel", "kernel", child_env(rehearsal), 900)
    out["failures"] = []
    for c in out["cases"]:
        if not c["ok"]:
            why = c.get("error") or (f"max error {c['max_err']:.4g} > "
                                     f"tolerance {c['tolerance']:.4g}")
            out["failures"].append(f"{c['name']}: {why}")
    return out


def phase_disagg(rehearsal: bool) -> dict:
    import asyncio

    from dynamo_tpu.sdk.config import load_config_file
    name = "config.cpu.yaml" if rehearsal else "config.yaml"
    cfg = load_config_file(os.path.join(HERE, "examples", "disagg", name))
    http_port, control = free_port(), free_port()
    cfg["Frontend"]["port"] = http_port
    os.makedirs(LOG_DIR, exist_ok=True)
    cfg_path = os.path.join(LOG_DIR, "disagg.config.json")
    with open(cfg_path, "w") as f:
        json.dump(cfg, f)
    model = cfg["DecodeWorker"]["model"]
    threshold = int(cfg["DecodeWorker"]["max_local_prefill_length"])
    argv = [sys.executable, "-m", "dynamo_tpu.sdk.serve",
            "examples.disagg.graph:Frontend", "-f", cfg_path,
            "--start-control-plane", "--control-port", str(control)]
    if not rehearsal:
        argv += ["--tpu-chips", "2"]
    t0 = time.monotonic()
    graph = Child("disagg", argv, child_env(rehearsal))
    try:
        graph.wait_line(lambda ln: ln.startswith("READY graph="), 900)
        out = {"seconds_to_ready": round(time.monotonic() - t0, 1)}
        workers = {}
        for ln in graph.lines:
            for svc in ("PrefillWorker", "DecodeWorker"):
                if ln and ln.startswith(f"[{svc}/0] READY"):
                    workers[svc] = ready_device(ln)
        out["workers"] = workers
        bad = [f"{svc} printed no READY device" for svc in
               ("PrefillWorker", "DecodeWorker") if svc not in workers]
        want = "cpu" if rehearsal else "tpu"
        for svc, dev in workers.items():
            if dev["platform"] != want:
                bad.append(f"{svc} runs on {dev['platform']}, not {want}")
        # the frontend discovers the model through the control plane
        deadline = time.monotonic() + 120
        while model not in http_get(http_port, "/v1/models"):
            if time.monotonic() > deadline:
                raise TimeoutError("frontend never listed the model")
            time.sleep(0.5)
        # one prompt under the local-prefill threshold, one over it (the
        # remote prefill + KV transfer path), one over it streamed
        short = max(TEMPLATE_TOKENS + 8, min(threshold // 2, 200))
        long_ = threshold + 100
        bodies = [
            make_body(model, "d0", short, 24, False, GREEDY, 1),
            make_body(model, "d1", long_, 24, False, GREEDY, 2),
            make_body(model, "d2", long_ + 64, 24, True, SAMPLED, 3),
        ]
        done = [(b, chat(http_port, b)) for b in bodies]
        bad += check_round(done)
        out["requests"] = [{"status": r.status, "error": r.error,
                            "tokens": r.completion_tokens,
                            "seconds": r.latency_s} for _, r in done]

        async def decode_stats():
            from dynamo_tpu.runtime.distributed import DistributedRuntime
            rt = await DistributedRuntime.connect("127.0.0.1", control)
            try:
                client = rt.namespace("dynamo-demo").component(
                    "backend").endpoint("generate").client()
                await client.start()
                try:
                    await client.wait_for_instances()
                except TimeoutError as e:
                    keys = [ent.key for ent in
                            await rt.kv.get_prefix("dynamo-demo/")]
                    raise RuntimeError(
                        f"{e}; the control plane holds {keys}") from e
                stats = await client.scrape_stats(timeout=10.0)
                await client.stop()
                return stats
            finally:
                await rt.shutdown()

        try:
            stats = asyncio.run(asyncio.wait_for(decode_stats(), 60))
        except Exception as e:
            stats = {}
            bad.append(f"decode worker stats scrape failed: "
                       f"{type(e).__name__}: {e}")
        disagg = [s.get("disagg") or {} for s in stats.values()]
        out["remote_prefills"] = sum(d.get("remote_prefills", 0)
                                     for d in disagg)
        out["local_prefills"] = sum(d.get("local_prefills", 0)
                                    for d in disagg)
        if out["remote_prefills"] < 1:
            bad.append(f"decode worker counted no remote prefill: {disagg}")
        if graph.proc.poll() is not None:
            bad.append(f"graph supervisor exited rc={graph.proc.returncode}")
        # a service that lost its lease (event loop starved past the TTL)
        # has dropped out of discovery even if these requests got through
        with open(os.path.join(LOG_DIR, "disagg.stderr.log")) as f:
            lost = [ln.strip() for ln in f if "lease lost" in ln]
        if lost:
            bad.append(f"a service lost its runtime lease: {lost[0]}")
        out.update(failures=bad, device=workers.get("DecodeWorker") or {})
        return out
    finally:
        graph.stop()


def phase_tp4(rehearsal: bool) -> dict:
    out = serve_and_check("tp4", "llama3-8b", ["--tp", "4"], rehearsal,
                          full=False, ready_timeout=900)
    dev = out["device"]
    peaks = dev.get("peak_bytes_in_use")
    # llama3-8b is 8.03e9 parameters, 16.1 GB in bf16: a chip that staged
    # the whole model would peak above 16 GB (it could not: the chip has
    # 16 GB). Spread over tp=4 each chip holds a quarter of the weights
    # plus a quarter of the KV pages, so the bound is half the model.
    model_bytes = 16.06e9
    if len(dev["devices"]) != 4 or dev["mesh"] != {"tp": 4}:
        out["failures"].append(f"engine mesh is {dev['mesh']} over "
                               f"{dev['devices']}, not tp=4 over 4 chips")
    if not peaks:
        out["failures"].append("backend reported no memory_stats")
    elif max(peaks) > model_bytes / 2 or min(peaks) < model_bytes / 8:
        out["failures"].append(
            f"peak_bytes_in_use per chip {peaks}: weights not spread "
            f"evenly over the four chips")
    return out


def cache_entries() -> int:
    """Files in the compile cache the children use (utils/launch.py)."""
    from dynamo_tpu.utils.launch import DEFAULT_CACHE_DIR
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    try:
        return sum(1 for n in os.listdir(d) if not n.startswith("."))
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# children (these import JAX; the parent never reaches this code)
# ---------------------------------------------------------------------------

def child_probe() -> dict:
    """What JAX finds, which versions, which native components load."""
    import importlib.metadata as md

    import jax
    from dynamo_tpu import native
    from dynamo_tpu.utils.launch import enable_compile_cache
    devs = jax.devices()
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    return {
        "platform": devs[0].platform, "device_kind": devs[0].device_kind,
        "device_count": len(devs), "versions": versions,
        "python": sys.version.split()[0],
        "compile_cache": {
            "dir": enable_compile_cache(),
            "placed_by_env": bool(
                os.environ.get("JAX_COMPILATION_CACHE_DIR"))},
        # built on demand with g++ from the .cpp beside them (native/
        # __init__.py); "python" means the pure-Python fallback is in use
        "native": {name: "library" if native.load(name) is not None
                   else "python"
                   for name in ("kv_indexer", "spm_bpe", "capi")},
    }


def child_kernel() -> dict:
    """Ragged Pallas decode kernel, COMPILED, against the XLA gather path.

    Serving shape: 8 rows, 16-page tables, ragged prefix lengths (empty,
    one token, page-aligned, nearly full), the pages of layer 1 of a
    two-layer stack, a shuffled page table. Each case runs twice: with
    zeros beyond each row's valid span, and with that stale space
    poisoned (NaN values in bf16 pages, NaN scales for int8 pages), which
    is what recycled pages can hold."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.attention import decode_attention_deferred
    from dynamo_tpu.ops.kv_quant import quantize_rows
    from dynamo_tpu.ops.paged_attention import (
        combine_self_attention, decode_paged_attention_prefix,
        kernel_supported,
    )
    from dynamo_tpu.utils.launch import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    s, pb, h, hkv, nl, layer = 8, 16, 32, 8, 2, 1
    # Both paths read the same bf16 (or int8) pages and return bf16. They
    # round in different places: the kernel scales q in f32 and feeds f32
    # operands to the MXU (bf16 passes), the gather path multiplies bf16
    # operands and rounds the probabilities to bf16 before the value dot.
    # Each rounding is <= 2^-8 relative and three or four compound, on
    # outputs of magnitude <= ~1 — so 2e-2 of the output scale; int8 adds
    # the gather path's rounding of dequantized rows to bf16 (3e-2).
    tol = {"bf16": 2e-2, "int8": 3e-2}
    # (name, head_dim, page_size): the registry's two geometries, plus the
    # 8-row block (page 16 x hd 64) that sits under the bf16 / int8
    # sublane tile and that kernel_supported admits
    geoms = [("llama3-1b", 64, 64), ("llama3-8b", 128, 64),
             ("rows8", 64, 16)]
    cases = []
    for name, hd, ps in geoms:
        assert kernel_supported(hd, ps), (name, hd, ps)
        p = s * pb + 32
        rng = np.random.default_rng(hd * 1000 + ps)
        cap = pb * ps
        lens = np.array([0, 1, ps, cap - 1, cap // 2 + 3, 5 * ps, 37,
                         cap - ps], np.int32)
        table = rng.permutation(p)[:s * pb].reshape(s, pb).astype(np.int32)
        q = jnp.asarray(rng.standard_normal((s, h, hd)) * 2, jnp.bfloat16)
        kn, vn = (jnp.asarray(rng.standard_normal((s, hkv, hd)),
                              jnp.bfloat16) for _ in range(2))
        kf, vf = (rng.standard_normal((nl, hkv, p, ps, hd))
                  .astype(np.float32) for _ in range(2))
        # stale[page, slot]: token slots no row's valid span covers
        stale = np.ones((p, ps), bool)
        for r in range(s):
            for i in range(pb):
                n_valid = int(np.clip(lens[r] - i * ps, 0, ps))
                stale[table[r, i], :n_valid] = False
        for quant in (False, True):
            for poison in (False, True):
                label = f"{name}/{'int8' if quant else 'bf16'}" \
                        f"/{'poisoned' if poison else 'clean'}"
                case = {"name": label, "head_dim": hd, "page_size": ps,
                        "tolerance": tol["int8" if quant else "bf16"]}
                cases.append(case)
                try:
                    if quant:
                        kq, ks = quantize_rows(jnp.asarray(kf))
                        vq, vs = quantize_rows(jnp.asarray(vf))
                        if poison:
                            mask = jnp.asarray(stale)[None, None]
                            ks = jnp.where(mask, jnp.nan, ks)
                            vs = jnp.where(mask, jnp.nan, vs)
                        kc, vc, scales = kq, vq, (ks, vs)
                    else:
                        fill = np.nan if poison else 0.0
                        kc, vc = (jnp.asarray(
                            np.where(stale[None, None, :, :, None], fill, x),
                            jnp.bfloat16) for x in (kf, vf))
                        scales = None
                    args = (q, kc, vc, jnp.array([layer], jnp.int32),
                            jnp.asarray(table), jnp.asarray(lens))

                    @jax.jit
                    def kernel(q, kc, vc, lyr, pt, ln, kn, vn, sc):
                        kw = {} if sc is None else dict(k_scale=sc[0],
                                                        v_scale=sc[1])
                        acc, m, l = decode_paged_attention_prefix(
                            q, kc, vc, lyr, pt, ln, interpret=False, **kw)
                        return combine_self_attention(q, kn, vn, acc, m, l)

                    @jax.jit
                    def gather(q, kc, vc, lyr, pt, ln, kn, vn, sc):
                        kw = {} if sc is None else dict(
                            k_scale=sc[0][layer], v_scale=sc[1][layer])
                        return decode_attention_deferred(
                            q, kc[layer], vc[layer], kn, vn, pt, ln, **kw)

                    got = np.asarray(jax.block_until_ready(
                        kernel(*args, kn, vn, scales)), np.float32)
                    ref = np.asarray(jax.block_until_ready(
                        gather(*args, kn, vn, scales)), np.float32)
                    scale = float(np.abs(ref).max())
                    err = float(np.abs(got - ref).max() / scale)
                    case.update(
                        max_err=err, out_scale=scale,
                        finite=bool(np.isfinite(got).all()
                                    and np.isfinite(ref).all()),
                        shape=list(got.shape))
                    case["ok"] = (case["finite"] and err <= case["tolerance"]
                                  and got.shape == (s, h, hd))
                    if not case["finite"]:
                        case["error"] = "non-finite output"
                except Exception as e:   # a Mosaic refusal fails the case
                    case.update(ok=False,
                                error=f"{type(e).__name__}: {str(e)[:600]}")
    return {"device": {"platform": dev.platform,
                       "device_kind": dev.device_kind,
                       "devices": [d.id for d in jax.devices()]},
            "interpret": False, "cases": cases}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

RUNNERS = {"aggregated": phase_aggregated, "kernel": phase_kernel,
           "disagg": phase_disagg, "tp4": phase_tp4}


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--rehearsal", action="store_true",
                    help="run `aggregated` and `disagg` at `tiny` on the "
                         "CPU; never a result for the chip")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of "
                         f"{','.join(PHASES)} (default: all)")
    ap.add_argument("--child", choices=("probe", "kernel"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.child:
        sys.path.insert(0, HERE)
        out = child_probe() if args.child == "probe" else child_kernel()
        print(json.dumps(out), flush=True)
        return 0

    if not os.path.isfile(os.path.join(HERE, "dynamo_tpu", "__init__.py")):
        log(f"no dynamo_tpu package beside {__file__}: run chip_smoke.py "
            f"from the root of a checkout")
        return 2
    sys.path.insert(0, HERE)
    wanted = [p.strip() for p in args.phases.split(",") if p.strip()]
    unknown = [p for p in wanted if p not in PHASES]
    if unknown:
        log(f"unknown phases {unknown}; have {list(PHASES)}")
        return 2

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        try:
            probe = run_json_child("probe", "probe",
                                   child_env(args.rehearsal), 300)
        except Exception as e:
            log(f"device probe failed: {type(e).__name__}: {e}")
            return 1
        if args.rehearsal and probe["platform"] != "cpu":
            log(f"rehearsal children must run on the cpu, probe found "
                f"{probe['platform']}")
            return 1
        if not args.rehearsal and probe["platform"] != "tpu":
            # no accelerator: no result, nonzero exit (stdout stays empty)
            log(f"JAX found no TPU (platform={probe['platform']!r}, "
                f"device_kind={probe['device_kind']!r}); chip_smoke.py "
                f"reports on the chip only. `--rehearsal` runs a CPU dry "
                f"run of the command.")
            return 1
        mode = {"rehearsal": True} if args.rehearsal else {}
        device = {"platform": probe["platform"],
                  "kind": probe["device_kind"],
                  "count": probe["device_count"]}
        print(json.dumps({**mode, "phase": "probe", "status": "passed",
                          **probe}), flush=True)
        statuses = {}
        for name in wanted:
            line = {**mode, "phase": name,
                    "platform": probe["platform"],
                    "device_kind": probe["device_kind"],
                    "device_count": probe["device_count"]}
            if args.rehearsal and name not in REHEARSAL_PHASES:
                line.update(status="skipped", reason="not part of the cpu "
                            "rehearsal: it needs the chip")
            elif not args.rehearsal \
                    and probe["device_count"] < CHIPS_NEEDED[name]:
                line.update(status="skipped", reason=(
                    f"needs {CHIPS_NEEDED[name]} chips, this machine has "
                    f"{probe['device_count']}"))
            else:
                t0 = time.monotonic()
                try:
                    out = RUNNERS[name](args.rehearsal)
                except Exception as e:
                    out = {"failures": [f"{type(e).__name__}: {e}"]}
                finally:
                    stop_all()
                want = "cpu" if args.rehearsal else "tpu"
                held = (out.get("device") or {}).get("platform")
                if held != want and not out["failures"]:
                    out["failures"].append(
                        f"the phase's process held platform {held!r}, "
                        f"not {want!r}")
                line.update(out, seconds=round(time.monotonic() - t0, 1),
                            status="failed" if out["failures"]
                            else "passed")
            statuses[name] = line["status"]
            print(json.dumps(line), flush=True)
        failed = [n for n, s in statuses.items() if s == "failed"]
        passed = [n for n, s in statuses.items() if s == "passed"]
        good = bool(passed) and not failed
        print(json.dumps({**mode, "summary": statuses,
                          "platform": probe["platform"],
                          "device_kind": probe["device_kind"]}), flush=True)
        if args.rehearsal:
            # never readable as a pass on the chip: ok is false by
            # construction, the rehearsal's own verdict has its own key
            print(json.dumps({"ok": False, "rehearsal": True,
                              "rehearsal_passed": good,
                              "device": device}), flush=True)
        elif good:
            print(json.dumps({"ok": True, "device": device}), flush=True)
        else:
            print(json.dumps({"ok": False, "device": device,
                              "failed": failed}), flush=True)
        return 0 if good else 1
    finally:
        stop_all()


if __name__ == "__main__":
    sys.exit(main())
