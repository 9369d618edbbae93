#!/usr/bin/env python
"""decode_profile: phase-attributed decode-loop profiling harness.

VERDICT r5 weak #2: decode throughput sat at ~60% (bf16) / ~45% (int8) of
the weight-bound roofline with the byte-independent remainder — host plan
building, per-window uploads, the blocking output fetch, commit/detok
bookkeeping — never attributed. This tool turns that gap into a measured
breakdown:

1. **Attribution pass** (pipeline_depth=1, engine.profile_sync=True): the
   engine's PhaseTimer splits each decode window's wall time into
   plan / upload / dispatch / wait / commit (under profile_sync `wait`
   is counted twice a window: the device's execution, then the output
   fetch), and the harness times detokenization of the emitted events —
   the full "plan/upload/dispatch/wait/commit/detok" split per window.
2. **Overlap pass** (pipeline_depth=2): the same workload through the
   overlapped pipeline; reports wall-time speedup, the pipeline occupancy
   counters (windows / overlapped / fallbacks / host syncs / plan
   uploads), and the host seconds that executed concurrently with device
   compute.
3. **Kernel + sampler attribution** (PR 18): each pass records which
   decode kernel served the device leg (`decode_kernel_tag`: ragged /
   gather / pp, "+fused" when the sampling tail ran in-program) and the
   one-dispatch-per-window invariant (`decode_dispatches`,
   `dispatches_per_window` — the unified ragged kernel keeps the common
   decode window at EXACTLY one device dispatch). The fused sampling
   tail never shows up in wait/commit (it runs inside the window
   program), so its cost is split out standalone: `sampler_tail` times
   the fused vs unfused tail at the same [slots, vocab] geometry.

The record is appended (append-only, final name — tools/artifacts.py
policy, VERDICT r5 weak #7) to DECODE_PROFILE.jsonl at the repo root.
Optionally wraps the timed loops in a jax.profiler trace (--trace-dir)
for op-level drill-down in TensorBoard/XProf.

Usage:
    JAX_PLATFORMS=cpu python tools/decode_profile.py            # tiny, CPU
    python tools/decode_profile.py --model llama3-1b --slots 8 \
        --decode-steps 64 --windows 20 --trace-dir /tmp/xprof
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from tools.artifacts import append_jsonl  # noqa: E402

DEFAULT_OUT = os.path.join(REPO_ROOT, "DECODE_PROFILE.jsonl")


def build_engine(args, depth: int):
    import dataclasses

    from dynamo_tpu.engine.config import (
        EngineConfig, ModelConfig, get_model_config,
    )
    from dynamo_tpu.engine.engine import NativeEngine

    if args.model == "tiny-f32":
        mcfg = ModelConfig(dtype="float32", max_model_len=2048)
    else:
        mcfg = get_model_config(args.model)
    if args.quant:
        mcfg = dataclasses.replace(mcfg, quant=args.quant)
    ecfg = EngineConfig(
        page_size=args.page_size,
        num_pages=args.num_pages,
        max_slots=args.slots,
        max_prefill_chunk=512,
        max_model_len=min(mcfg.max_model_len, 2048),
        decode_steps=args.decode_steps,
        pipeline_depth=depth,
    )
    return NativeEngine(mcfg, ecfg, seed=0)


def run_pass(args, depth: int, profile_sync: bool, trace_dir=None) -> dict:
    """One measured decode run; returns phases + counters + wall time."""
    import jax

    from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams

    eng = build_engine(args, depth)
    max_tokens = args.windows * args.decode_steps
    # --sampled drives the fused-tail path (seeded, top_p = 1) so the
    # device leg carries the "+fused" kernel tag; default stays greedy
    # for comparability with pre-PR-18 records
    params = SamplingParams(
        max_tokens=max_tokens, ignore_eos=True,
        temperature=0.8 if args.sampled else 0.0,
        top_k=40 if args.sampled else 0,
        seed=1234 if args.sampled else 0)
    for i in range(args.slots):
        prompt = [(131 * i + j) % (eng.model_cfg.vocab_size - 1) + 1
                  for j in range(args.prompt_len)]
        eng.add_request(EngineRequest(f"p{i}", prompt, params))
    # warmup: prefill + two windows so every program is compiled before
    # the timed loop (first-use XLA compiles would swamp the phases)
    while eng.scheduler.waiting:
        eng.step()
    for _ in range(2):
        eng.step()
    eng.phases.reset()
    eng.profile_sync = profile_sync

    detok_buf = []
    tokens = 0
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    while eng.has_work():
        events = eng.step()
        # the detokenize leg of the commit path: what llm/worker.py does
        # with each event before the bytes can leave the process
        with eng.phases.phase("detok"):
            for ev in events:
                if ev.token is not None:
                    detok_buf.append(f"<{ev.token}>")
                    tokens += 1
    wall = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()

    return {
        "depth": depth,
        "profile_sync": profile_sync,
        "wall_s": round(wall, 4),
        "tokens": tokens,
        "tok_s": round(tokens / wall, 1) if wall else 0.0,
        "phases": eng.phases.split(),
        # which kernel served the device leg ("ragged"/"gather"/"pp",
        # "+fused" when the sampling tail ran inside the window program)
        "decode_kernel_tag": eng.decode_kernel_tag,
        "counters": {
            "decode_windows": eng.decode_windows,
            "decode_dispatches": eng.decode_dispatches,
            "pipeline_windows": eng.pipeline_windows,
            "pipeline_overlapped": eng.pipeline_overlapped,
            "pipeline_fallbacks": eng.pipeline_fallbacks,
            "decode_host_syncs": eng.decode_host_syncs,
            "decode_plan_uploads": eng.decode_plan_uploads,
        },
        # the PR-18 invariant: the common decode window is ONE dispatch
        "dispatches_per_window": round(
            eng.decode_dispatches / eng.decode_windows, 4)
        if eng.decode_windows else 0.0,
    }


def sampler_tail_split(args, vocab_size: int) -> dict:
    """Standalone fused-vs-unfused sampling-tail timing at the decode
    geometry [slots, vocab]. Inside a fused window the tail's cost rides
    the device leg (the fetch and commit never see it), so attribution needs the
    tail measured on its own: `unfused_ms` is the full sort + double
    argsort + softmax-cumsum tail, `fused_ms` the single-argsort rank
    tail the common path dispatches (docs/PERF.md §3g)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import sampler

    rng = np.random.default_rng(0)
    b = args.slots
    logits = jnp.asarray(rng.standard_normal((b, vocab_size)), jnp.float32)
    temp = jnp.full((b,), 0.8, jnp.float32)
    top_k = jnp.full((b,), 40, jnp.int32)
    top_p = jnp.ones((b,), jnp.float32)
    keys = sampler.make_keys(jnp.arange(b, dtype=jnp.int32),
                             jnp.zeros((b,), jnp.int32))

    fused_fn = jax.jit(sampler.sample_fused)
    unfused_fn = jax.jit(sampler.sample)

    def timed(fn, *a):
        fn(*a).block_until_ready()          # compile outside the clock
        reps = 20
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*a)
        out.block_until_ready()
        return (time.perf_counter() - t0) / reps * 1e3

    fused_ms = timed(fused_fn, logits, temp, top_k, keys)
    unfused_ms = timed(unfused_fn, logits, temp, top_k, top_p, keys)
    return {
        "batch": b,
        "vocab": vocab_size,
        "fused_ms": round(fused_ms, 4),
        "unfused_ms": round(unfused_ms, 4),
        "fused_over_unfused": round(fused_ms / unfused_ms, 4)
        if unfused_ms else 0.0,
    }


def run_stream_pass(args) -> dict:
    """Streamed long-context attribution (PERF.md §3h): one sequence
    whose context is ~4x the HBM page budget, driven through the
    tiered-KV streaming decode with profile_sync semantics (the stream
    loop is host-driven, so its phases are already synchronous). The
    PhaseTimer's `prefetch` phase isolates the double-buffer staging
    leg; the stream counters qualify it — a hit-dominated run means
    those seconds were ahead-of-consume copies, a late-dominated run
    means the tier is slower than the decode cadence and the staging
    time sat on the critical path."""
    from dynamo_tpu.engine.config import (
        EngineConfig, ModelConfig, get_model_config,
    )
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
    from dynamo_tpu.engine.streaming import STREAM_STATS

    if args.model == "tiny-f32":
        mcfg = ModelConfig(dtype="float32", max_model_len=2048)
    else:
        mcfg = get_model_config(args.model)
    page = args.stream_page_size
    max_tokens = 8 * page
    total_pages = -(-(args.stream_prompt_len + max_tokens) // page)
    budget = max(total_pages // 4, 6)          # context = ~4x HBM budget
    ecfg = EngineConfig(
        page_size=page, num_pages=budget, max_slots=2,
        max_prefill_chunk=8 * page,
        prefill_buckets=(2 * page, 4 * page, 8 * page),
        max_model_len=mcfg.max_model_len,
        host_pages=2 * total_pages, stream_pages=4,
        stream_resident_pages=max(budget - 2, 4), stream_hot_pages=2)
    eng = NativeEngine(mcfg, ecfg, seed=0)
    prompt = [(7 * i + 3) % (mcfg.vocab_size - 1) + 1
              for i in range(args.stream_prompt_len)]
    eng.add_request(EngineRequest("stream", prompt, SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True)))
    s0 = STREAM_STATS.snapshot()
    eng.phases.reset()
    tokens = 0
    t0 = time.perf_counter()
    while eng.has_work():
        for ev in eng.step():
            if ev.token is not None:
                tokens += 1
    wall = time.perf_counter() - t0
    s1 = STREAM_STATS.snapshot()
    delta = {k: s1[k] - s0[k] for k in s1}
    hits, lates = delta["prefetch_hit"], delta["prefetch_late"]
    phases = eng.phases.split()
    return {
        "context_tokens": args.stream_prompt_len + max_tokens,
        "hbm_budget_pages": budget,
        "context_pages": total_pages,
        "wall_s": round(wall, 4),
        "tokens": tokens,
        "tok_s": round(tokens / wall, 1) if wall else 0.0,
        "phases": phases,
        "prefetch_s": round(
            phases.get("prefetch", {}).get("seconds", 0.0), 4),
        "stream_counters": delta,
        "prefetch_hit_ratio": round(hits / (hits + lates), 4)
        if hits + lates else 0.0,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="tiny-f32",
                    help="registry name, or tiny-f32 (default: CPU-sized)")
    ap.add_argument("--quant", default="", help="'' or int8")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--windows", type=int, default=12,
                    help="decode windows per request in the timed loop")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--num-pages", type=int, default=512)
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="append-only JSONL artifact (final name)")
    ap.add_argument("--trace-dir", default=None,
                    help="also capture a jax.profiler trace here")
    ap.add_argument("--sampled", action="store_true",
                    help="seeded sampling (top_p=1): the fused-tail path")
    ap.add_argument("--no-stream", action="store_true",
                    help="skip the streamed long-context pass (PERF.md §3h)")
    ap.add_argument("--stream-prompt-len", type=int, default=320,
                    help="prompt length for the streamed pass (its HBM "
                         "budget is derived as ~1/4 of the context pages)")
    ap.add_argument("--stream-page-size", type=int, default=4,
                    help="page size for the streamed pass (small pages "
                         "keep the tiny-CPU stream geometry meaningful)")
    args = ap.parse_args(argv)

    import jax

    # 1. attribution: synchronous loop, device time isolated per phase
    attribution = run_pass(args, depth=1, profile_sync=True,
                           trace_dir=args.trace_dir)
    # 2. overlap: the pipelined loop on the same workload
    pipelined = run_pass(args, depth=2, profile_sync=False)
    # 3. the sampling tail, split out of the window program (PR 18)
    from dynamo_tpu.engine.config import ModelConfig, get_model_config
    vocab = (ModelConfig().vocab_size if args.model == "tiny-f32"
             else get_model_config(args.model).vocab_size)
    sampler_tail = sampler_tail_split(args, vocab)
    # 4. the streamed long-context leg: prefetch-phase attribution for
    # decode beyond the HBM page budget (PERF.md §3h)
    stream = None if args.no_stream else run_stream_pass(args)

    host_phases = ("plan", "upload", "commit", "detok")
    hidden_s = sum(pipelined["phases"].get(p, {}).get("seconds", 0.0)
                   for p in host_phases)
    c = pipelined["counters"]
    record = {
        "t": time.time(),
        "argv": vars(args),
        "jax": jax.__version__,
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "attribution": attribution,
        "pipelined": pipelined,
        "sampler_tail": sampler_tail,
        "stream": stream,
        "overlap": {
            # host seconds that executed while the device ran a window
            "host_s_overlapped_with_device": round(hidden_s, 4),
            "overlap_fraction": round(
                c["pipeline_overlapped"] / c["pipeline_windows"], 4)
            if c["pipeline_windows"] else 0.0,
            "speedup": round(
                attribution["wall_s"] / pipelined["wall_s"], 3)
            if pipelined["wall_s"] else 0.0,
        },
    }
    append_jsonl(args.out, record)
    print(json.dumps(record["overlap"]))
    print(f"appended record to {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
