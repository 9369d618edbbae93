#!/usr/bin/env python3
"""Time the one-token state update of a decode step's power-retention
layers: the slot-addressed kernel, by the block of the state it holds.

    chiprun -- python3 tools/retention_step_bench.py          # the chip
    python3 tools/retention_step_bench.py --rehearsal         # tiny, CPU

At `brumby-14b`'s served shapes (8 layers, 16 + 1 state slots and the
scratch slot, 8 key-value heads of 128 x 8320 float32 with five query heads
each, 16 rows at a random permutation of the slots): a scan over the layers
that carries both state leaves, as `models/llama.decode_forward` does, each
layer's update by `ops/power_retention.retention_step_slots` with
  hbNfM    the Pallas kernel, N key-value heads x M features of a row a
           grid step (`--blocks 1x1664,1x8320,...`),
  gather   the rows' states gathered by slot, `retention_step`, scattered
           back (`impl="plain"`: what a CPU takes), over the first layer
           alone: at the served size it holds a second 545 MB of state a
           layer, which is why it is not served.
One JSON line a reading: milliseconds a call of all layers (median of 20
after 3 warm calls), the bytes the floor moves (every row's state once each
way, a layer; operands left out) and their time at 819 GB/s, and the
largest difference of `o` and of the touched states from the gather form
on the first layer. PERF.md section 6, PR 55 quotes its output. A time
comes from the chip only.
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

from dynamo_tpu.ops import power_retention as pr   # noqa: E402

HBM_BYTES_PER_S = 819e9


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--blocks", default="1x640,1x1664,1x8320,2x1664,8x640,"
                    "8x128")
    args = ap.parse_args()
    if args.rehearsal:
        layers, slots, hkv, grp, d, rows = 2, 5, 2, 2, 16, 4
        blocks = [(1, pr.features(d)), (2, pr.features(d))]
        impl = "interpret"
    else:
        layers, slots, hkv, grp, d, rows = 8, 17, 8, 5, 128, 16
        blocks = [tuple(int(n) for n in b.split("x"))
                  for b in args.blocks.split(",")]
        impl = "pallas"
    f = pr.features(d)
    rng = np.random.default_rng(0)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    at = jnp.asarray(rng.permutation(slots)[:rows], jnp.int32)
    q, k, v = arr(rows, hkv * grp, d), arr(rows, hkv, d), arr(rows, hkv, d)
    log_g = jnp.log(jax.nn.sigmoid(3.0 + arr(rows, hkv)))
    moved = 2 * rows * layers * hkv * (d * f + f) * 4

    def run(n_layers, **how):
        def call(ret_s, ret_z):
            def body(carry, l):
                o, s, z = pr.retention_step_slots(
                    *carry, l, at, q, k, v, log_g, **how)
                return (s, z), o
            return jax.lax.scan(body, (ret_s, ret_z),
                                jnp.arange(n_layers, dtype=jnp.int32))
        return jax.jit(call, donate_argnums=(0, 1))

    def leaves():
        # drawn on the device: the leaf is 4.9 GB at the served size
        key = jax.random.PRNGKey(1)
        return (jax.random.normal(key, (layers, slots + 1, hkv, d, f),
                                  jnp.float32),
                jnp.abs(jax.random.normal(key, (layers, slots + 1, hkv, f),
                                          jnp.float32)))

    def first_layer(fn):
        (s, z), o = fn(*leaves())
        out = (np.asarray(o[0]), np.asarray(s[0, at]), np.asarray(z[0, at]))
        del s, z
        return out

    want = first_layer(run(1, impl="plain"))
    for hb, fb in blocks:
        name = f"hb{hb}f{fb}"
        try:
            fn = run(layers, impl=impl, heads_per_block=hb,
                     features_per_block=fb)
            got = first_layer(fn)
            state = leaves()
            times = []
            for i in range(23):
                t0 = time.perf_counter()
                state, o = fn(*state)
                jax.block_until_ready(o)
                times.append(time.perf_counter() - t0)
            del state
            ms = statistics.median(times[3:]) * 1e3
            print(json.dumps({
                "form": name, "ms": round(ms, 3),
                "floor_bytes": moved,
                "floor_ms": round(moved / HBM_BYTES_PER_S * 1e3, 3),
                "roofline": round(moved / HBM_BYTES_PER_S * 1e3 / ms, 4),
                "o_diff": float(np.abs(got[0] - want[0]).max()),
                "s_diff": float(np.abs(got[1] - want[1]).max()),
                "z_diff": float(np.abs(got[2] - want[2]).max()),
                "backend": jax.default_backend()}), flush=True)
        except Exception as e:   # a block the compiler refuses is a reading
            print(json.dumps({"form": name, "error": str(e)[:400]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
