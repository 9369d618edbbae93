#!/usr/bin/env python3
"""Time a mixed step's attention ALONE, over its grid against over its rows.

    chiprun -- python3 tools/attention_rows_bench.py          # the chip
    python3 tools/attention_rows_bench.py --rehearsal         # tiny, CPU

For each (rows, chunk, kv heads, group, head width, keys) a benchmark
cell's mixed step runs, with the plan that cell usually holds (its decode
rows at one token each beside the whole chunks of its usual step), on gathered
K / V of the cell's own shape in bfloat16: `ops/attention.attend` over
the `[rows, chunk]` grid (the form before PR 47, and the grid branch
since) against `ops/attention.attention_rows` over the step's flat rows
(the compact branch's form: one chunk row a loop pass). Eight calls
chained in one scan, as a layer scan chains them; one JSON line a
reading, milliseconds a layer (median of 10 after 2 warm calls).
PERF.md section 6, PR 47 quotes its output. A time comes from the chip
only. It times the two forms on operands that are already there: what a
step pays to hand the gathered K / V to the `cond` the row form sits in
(a copy of both leaves a reader, which is what decides the [32, 16]
shapes: `ops/attention.attention_rows_pay`) is not in these numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax   # noqa: E402
import jax.numpy as jnp   # noqa: E402
import numpy as np   # noqa: E402

from dynamo_tpu.ops import attention as attn   # noqa: E402

LAYERS = 8
# name: rows, chunk, kv heads, query heads a kv head, head width, keys,
# one-leaf (the keys are the values), chunk rows of the cell's usual step
SHAPES = {
    "mistral-7b [32,16]": (32, 16, 8, 4, 128, 768, False, 1),
    "olmoe-1b-7b [32,16]": (32, 16, 16, 1, 128, 768, False, 1),
    "moonlight-16b-a3b [8,64]": (8, 64, 1, 16, 576, 4096, True, 1),
    "mellum2 full [8,64]": (8, 64, 4, 8, 128, 4096, False, 1),
    "mellum2 window [8,64]": (8, 64, 4, 8, 128, 1152, False, 1),
    "trinity window [8,64]": (8, 64, 4, 8, 128, 2176, False, 1),
    "falcon-h1-34b [64,64]": (64, 64, 4, 5, 128, 768, False, 3),
    "ling latent [64,64]": (64, 64, 1, 32, 576, 768, True, 3),
}
REHEARSAL = {"tiny [16,16]": (16, 16, 2, 2, 16, 64, False, 2)}


def plan(rows, chunk, keys, chunk_rows):
    """Decode rows at the last key beside `chunk_rows` whole chunks that
    end there. -> (kv_lens, positions, valid), NumPy."""
    valid = np.zeros((rows, chunk), bool)
    valid[:, 0] = True
    valid[rows - chunk_rows:] = True
    kv_lens = np.full((rows,), keys, np.int32)
    positions = np.full((rows, chunk), keys - 1, np.int32)
    positions[rows - chunk_rows:] = keys - chunk + np.arange(chunk)
    return kv_lens, positions, valid


def time_ms(fn, *args, calls=10, warm=2):
    times = []
    for _ in range(warm + calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1000 * statistics.median(times[warm:]) / LAYERS


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="chiprun_out/attention_rows_bench.jsonl")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearsal:
        sys.exit("no TPU: a time comes from the chip only (--rehearsal "
                 "runs the control flow at a tiny size)")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    for name, shape in (REHEARSAL if args.rehearsal else SHAPES).items():
        rows, chunk, hkv, g, hd, keys, one_leaf, chunk_rows = shape
        kv_lens, positions, valid = plan(rows, chunk, keys, chunk_rows)
        n_real = int(valid.sum())
        width, fits = attn.compact_step(np.where(valid, 0, -1))
        assert fits, (name, n_real, width)
        key = jax.random.PRNGKey(0)
        k = jax.random.normal(key, (hkv, rows, keys, hd), jnp.bfloat16)
        v = None if one_leaf else jax.random.normal(
            jax.random.fold_in(key, 1), k.shape, jnp.bfloat16)
        q = jax.random.normal(jax.random.fold_in(key, 2),
                              (rows, chunk, hkv * g, hd), jnp.bfloat16)
        lens, pos, ok = (jnp.asarray(a) for a in (kv_lens, positions, valid))
        start = (np.cumsum(valid.reshape(-1)) - 1)[np.arange(rows) * chunk]
        step = attn.step_rows(ok, jnp.asarray(start))
        cells = np.flatnonzero(valid.reshape(-1))
        q_flat = jnp.zeros((width,) + q.shape[2:], q.dtype).at[
            :n_real].set(q.reshape((-1,) + q.shape[2:])[cells])

        def chained(form):
            def run(q0, k, v):
                return jax.lax.scan(lambda q, _: (form(q, k, v), None), q0,
                                    None, length=LAYERS)[0]
            return jax.jit(run)

        reading = {"shape": name, "real_queries": n_real,
                   "grid_queries": rows * chunk, "flat_rows": width,
                   "chunk_rows": chunk_rows, "device": device.device_kind}
        reading["grid_ms_a_layer"] = time_ms(chained(
            lambda q, k, v: attn.attend(q, k, v, lens, pos)), q, k, v)
        reading["rows_ms_a_layer"] = time_ms(chained(
            lambda q, k, v: attn.attention_rows(q, k, v, lens, pos, step,
                                                ok)), q_flat, k, v)
        line = json.dumps(reading)
        print(line, flush=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
