#!/usr/bin/env python3
"""Time ONE layer of a mixed step over its grid against over its real tokens.

    chiprun -- python3 tools/compact_layer_bench.py           # the chip
    python3 tools/compact_layer_bench.py --rehearsal          # tiny, CPU

For each of the three MLP kinds the benchmark serves (dense: mistral-7b;
capacity-form experts: mixtral-8x7b; dropless experts: olmoe-1b-7b), at the
published widths and a [32, 16] mixed plan with 31 decode rows and one
16-token chunk real: `models/llama.forward()` over the grid with the last
logits gathered afterwards (the arithmetic before PR 32) and
`forward(last_idx=...)`, the engine's step (its token-wise halves over 128
flat rows). Each is timed at 1 and at 3 layers (2 where three of
Mixtral's do not fit beside their own initialisation); the difference
a layer is one layer with embedding, head and dispatch cancelled. One
JSON line a reading, milliseconds a call (median of 20 after 3 warm calls). PERF.md
section 6, PR 32 quotes its output. A time comes from the chip only.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax   # noqa: E402
import jax.numpy as jnp   # noqa: E402
import numpy as np   # noqa: E402

from dynamo_tpu.models import llama   # noqa: E402
from dynamo_tpu.models.loader import config_from_hf   # noqa: E402

ROWS, CHUNK, TABLE, PAGE = 32, 16, 12, 64
KINDS = {"dense": "mistral-7b", "capacity": "mixtral-8x7b",
         "dropless": "olmoe-1b-7b"}
DEPTHS = {"capacity": (1, 2)}      # the others: (1, 3)
REHEARSAL = {"dense": "rehearsal-tiny", "capacity": "rehearsal-tiny-moe",
             "dropless": "rehearsal-tiny-olmoe"}


def mixed_plan(vocab):
    """31 decode rows at position 200 + one 16-token chunk from 192."""
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, vocab, (ROWS, CHUNK)).astype(np.int32)
    page_table = np.arange(ROWS * TABLE, dtype=np.int32).reshape(ROWS, TABLE)
    positions = np.full((ROWS, CHUNK), 200, np.int32)
    write_idx = np.full((ROWS, CHUNK), -1, np.int32)
    at = np.arange(192, 192 + CHUNK)
    positions[-1] = at
    write_idx[:-1, 0] = page_table[:-1, 200 // PAGE] * PAGE + 200 % PAGE
    write_idx[-1] = page_table[-1, at // PAGE] * PAGE + at % PAGE
    kv_lens = np.full((ROWS,), 201, np.int32)
    kv_lens[-1] = 192 + CHUNK
    last = np.zeros((ROWS,), np.int32)
    last[-1] = CHUNK - 1
    return tuple(jnp.asarray(a) for a in (
        tokens, positions, page_table, kv_lens, write_idx, last))


def time_calls(fn, params, cache, plan, calls=20, warm=3):
    times = []
    for i in range(warm + calls):
        t0 = time.perf_counter()
        out, cache = fn(params, cache, *plan)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    return 1000 * statistics.median(times[warm:])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--kinds", default=",".join(KINDS))
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearsal:
        sys.exit("no TPU: a time comes from the chip only (--rehearsal "
                 "runs the control flow at a tiny size)")
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for kind in args.kinds.split(","):
        name = (REHEARSAL if args.rehearsal else KINDS)[kind]
        with open(os.path.join(here, "benchmark", "configs", name,
                               "config.json")) as f:
            base = config_from_hf(json.load(f), name=name)
        reading = {"kind": kind, "config": name, "plan": [ROWS, CHUNK],
                   "real_tokens": ROWS - 1 + CHUNK,
                   "flat_rows": llama.step_compaction(
                       np.zeros((ROWS, CHUNK), np.int32))[0],
                   "device": device.device_kind}
        depths = DEPTHS.get(kind, (1, 3))
        for layers in depths:
            cfg = dataclasses.replace(base, num_layers=layers)
            # jitted: the draw fuses into the bf16 store, no f32 copy
            params = jax.jit(lambda cfg=cfg: llama.init_params(
                jax.random.PRNGKey(0), cfg))()
            plan = mixed_plan(cfg.vocab_size)

            def grid(params, cache, tokens, positions, page_table, kv_lens,
                     write_idx, last, cfg=cfg):
                logits, cache = llama.forward(
                    params, cfg, tokens, cache, llama.AttnMetadata(
                        positions, page_table, kv_lens, write_idx))
                return logits[jnp.arange(ROWS), last], cache

            def step(params, cache, tokens, positions, page_table, kv_lens,
                     write_idx, last, cfg=cfg):
                return llama.forward(
                    params, cfg, tokens, cache, llama.AttnMetadata(
                        positions, page_table, kv_lens, write_idx),
                    last_idx=last)

            for label, fn in (("grid", grid), ("step", step)):
                cache = llama.init_cache(cfg, ROWS * TABLE, PAGE)
                reading[f"{label}_ms_{layers}_layers"] = time_calls(
                    jax.jit(fn, donate_argnums=(1,)), params, cache, plan)
            del params
        for label in ("grid", "step"):
            reading[f"{label}_ms_a_layer"] = (
                reading[f"{label}_ms_{depths[1]}_layers"]
                - reading[f"{label}_ms_{depths[0]}_layers"]
            ) / (depths[1] - depths[0])
        print(json.dumps(reading), flush=True)


if __name__ == "__main__":
    main()
