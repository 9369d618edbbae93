#!/usr/bin/env python3
"""Time the one-token state update of a decode step's linear layers: the
slot-addressed kernel against the gather / update / scatter form.

    chiprun -- python3 tools/linattn_step_bench.py            # the chip
    python3 tools/linattn_step_bench.py --rehearsal           # tiny, CPU

At `ling-3.0-flash-vl`'s served shapes (7 linear layers, 64 + 3 state slots
and the scratch slot, 32 heads of 128 x 128 float32, 64 rows at a random
permutation of the slots): a scan over the layers that carries the state
leaf, as `models/llama.decode_forward` does, with each layer's update by
  gather   the rows' states gathered by slot, `kda_step`, scattered back:
           the served form until PR 34, and what a CPU still takes
           (`kda_step_slots(impl="plain")`),
  hbN      `ops/linear_attention.kda_step_slots`, the Pallas kernel, N
           heads of a row a grid step.
One JSON line a reading: milliseconds a call of all layers (median of 20
after 3 warm calls), the bytes the floor moves (every row's state once each
way, a layer) and their time at 819 GB/s, and the largest difference of `o`
and of the touched states from the gather form. PERF.md section 6, PR 34
quotes its output. A time comes from the chip only.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax   # noqa: E402
import jax.numpy as jnp   # noqa: E402
import numpy as np   # noqa: E402

from dynamo_tpu.ops import linear_attention as la   # noqa: E402

HBM_BYTES_PER_S = 819e9


# gather by slot, `kda_step`, scatter: what `kda_step_slots` is off the chip
gather_form = functools.partial(la.kda_step_slots, impl="plain")


def layers_of(update):
    """All layers' updates in one program, the leaf carried and donated."""
    def run(kda_s, slots, ops):
        def layer(kda_s, xs):
            lk, (q, k, v, g, beta) = xs
            o, kda_s = update(kda_s, lk, slots, q, k, v, g, beta)
            return kda_s, o
        n = kda_s.shape[0]
        return jax.lax.scan(layer, kda_s,
                            (jnp.arange(n, dtype=jnp.int32), ops))
    return jax.jit(run, donate_argnums=(0,))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shapes, the kernel in the interpreter")
    ap.add_argument("--heads-per-block", default="8,16,32")
    args = ap.parse_args(argv)
    layers, slots_n, h, d, b = 7, 68, 32, 128, 64
    impl, reps = "pallas", 20
    blocks = [int(x) for x in args.heads_per_block.split(",")]
    if args.rehearsal:
        layers, slots_n, h, d, b, impl, reps = 2, 12, 4, 16, 8, "interpret", 2
        blocks = [2, 4]
    elif jax.default_backend() != "tpu":
        print("no TPU attached: --rehearsal runs here", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)

    def f(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)
    ops = (la.l2_normalize(f(layers, b, h, d)) * d ** -0.5,
           la.l2_normalize(f(layers, b, h, d)), f(layers, b, h, d),
           -5.0 * jax.nn.sigmoid(f(layers, b, h, d)),
           jax.nn.sigmoid(f(layers, b, h)))
    slots = jnp.asarray(rng.permutation(slots_n - 1)[:b], jnp.int32)
    start = np.asarray(f(layers, slots_n, h, d, d))
    floor = 2 * layers * b * h * d * d * 4
    forms = {"gather": gather_form}
    forms.update({f"hb{n}": functools.partial(
        la.kda_step_slots, impl=impl, heads_per_block=n) for n in blocks})
    want = None
    for name, update in forms.items():
        run = layers_of(update)
        kda_s, o = run(jnp.asarray(start), slots, ops)
        got = (np.asarray(o), np.asarray(kda_s))
        want = want or got
        times = []
        for i in range(reps + 3):
            t0 = time.perf_counter()
            kda_s, o = run(kda_s, slots, ops)
            jax.block_until_ready((kda_s, o))
            times.append(time.perf_counter() - t0)
        print(json.dumps({
            "form": name, "device": jax.devices()[0].device_kind,
            "ms": round(1e3 * statistics.median(times[3:]), 3),
            "floor_mb": round(floor / 1e6, 1),
            "floor_ms": round(1e3 * floor / HBM_BYTES_PER_S, 3),
            "o_diff": float(np.abs(got[0] - want[0]).max()),
            "state_diff": float(np.abs(got[1] - want[1]).max()),
            "untouched_equal": bool(np.array_equal(
                got[1][:, -1], start[:, -1]))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
