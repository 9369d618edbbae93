"""Time the forms in which a step's small host operands can reach the
device, on whatever device JAX finds.

    chiprun -- python3 tools/upload_bench.py        # the chip
    python3 tools/upload_bench.py                   # here: the CPU's host cost

At the operand sets the benchmark's cells run (a `[32,16]` mixed step over
a 12-page table: twelve arrays; a decode window's fresh plan over 32 slots:
ten arrays and a three-array carry), one JSON line a (set, form, contended)
reading, milliseconds a step, median of 200 after 20 warm steps:

  parent  one `jnp.asarray` an operand, then the jitted call (the code
          before PR 30)
  a       the NumPy arrays handed straight to the jitted call
  b       one `jax.device_put` of the whole tuple, then the call
  c       `engine.pack_operands` into ONE int32 buffer, one
          `jax.device_put` of it, the call unpacking it by static slices
          (`engine.unpack_operands`)
  c-numpy the packed buffer handed to the call as NumPy

`stage_ms` is the host time until the operands are staged (what the
engine's `upload` phase holds), `dispatch_ms` the jitted call's return,
`step_ms` the two plus the wait for the result: a step is synchronous, so
all of it is time the chip waits. `buffers` counts host-to-device buffers
a step. `--contend` repeats every reading beside a thread that does what
the serving process's event loop does between two steps (Python work that
lets go of the GIL at every write), because the engine's step runs in an
executor thread of that process and every trip through Python's transfer
path gives the GIL away. PERF.md section 6, PR 30 quotes its output; no
cell runs it.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax   # noqa: E402
import jax.numpy as jnp   # noqa: E402
import numpy as np   # noqa: E402

from dynamo_tpu.engine.engine import (   # noqa: E402
    pack_operands, unpack_operands,
)

ROWS, CHUNK, PAGES, STOPS = 32, 16, 12, 1


def operand_sets(rng) -> dict:
    """The cells' operand sets, in the order the programs take them."""
    def i32(*shape, high=1 << 20):
        return rng.integers(0, high, shape, dtype=np.int32)

    def f32(*shape):
        return rng.random(shape, dtype=np.float32)

    sampling = (f32(ROWS), i32(ROWS, high=50), f32(ROWS), i32(ROWS))
    return {
        # tokens, positions, page_table, kv_lens, write_idx, last_idx,
        # temp, top_k, top_p, seeds, counters, min_toks
        "mixed_step": (i32(ROWS, CHUNK), i32(ROWS, CHUNK), i32(ROWS, PAGES),
                       i32(ROWS), i32(ROWS, CHUNK) - 1, i32(ROWS, high=CHUNK),
                       *sampling, i32(ROWS), i32(ROWS, high=4)),
        # page_table, base_table, max_pos, temp, top_k, top_p, seeds,
        # min_toks, ignore_eos, stop_ids; then the carry: token, position,
        # counter
        "fresh_window": (i32(ROWS, PAGES), i32(ROWS, PAGES), i32(ROWS),
                         *sampling, i32(ROWS, high=4),
                         rng.random(ROWS) < 0.5, i32(ROWS, STOPS) - 1,
                         i32(ROWS), i32(ROWS), i32(ROWS)),
    }


def consume(*ops):
    """A program that reads every operand and costs the device nothing."""
    return sum(jnp.sum(o.astype(jnp.float32)) for o in ops)


def forms(ops) -> dict:
    """name -> (stage, call, buffers): `call(stage())` is one step."""
    plain = jax.jit(consume)
    packed = jax.jit(lambda layout, buf: consume(
        *unpack_operands(layout, buf)), static_argnums=(0,))

    def stage_packed(put):
        def stage():
            layout, buf = pack_operands(ops)
            return layout, put(buf)
        return stage

    return {
        "parent": (lambda: tuple(jnp.asarray(o) for o in ops),
                   lambda staged: plain(*staged), len(ops)),
        "a": (lambda: ops, lambda staged: plain(*staged), len(ops)),
        "b": (lambda: jax.device_put(ops),
              lambda staged: plain(*staged), len(ops)),
        "c": (stage_packed(jax.device_put),
              lambda staged: packed(*staged), 1),
        "c-numpy": (stage_packed(lambda buf: buf),
                    lambda staged: packed(*staged), 1),
    }


def time_form(stage, call, steps: int, warm: int) -> dict:
    stage_s, dispatch_s, step_s = [], [], []
    for i in range(warm + steps):
        t0 = time.perf_counter()
        staged = stage()
        t1 = time.perf_counter()
        out = call(staged)
        t2 = time.perf_counter()
        out.block_until_ready()
        t3 = time.perf_counter()
        if i >= warm:
            stage_s.append(t1 - t0)
            dispatch_s.append(t2 - t1)
            step_s.append(t3 - t0)
    return {"stage_ms": 1e3 * statistics.median(stage_s),
            "dispatch_ms": 1e3 * statistics.median(dispatch_s),
            "step_ms": 1e3 * statistics.median(step_s),
            "step_p90_ms": 1e3 * statistics.quantiles(step_s, n=10)[-1]}


def event_loop_stand_in(stop: threading.Event) -> None:
    """What the serving process's other thread does while the engine
    stages a step: format a frame, write it (the write lets go of the
    GIL), again for each of 32 streams, then a short sleep."""
    with open(os.devnull, "wb") as sink:
        while not stop.is_set():
            for i in range(ROWS):
                frame = json.dumps({"id": i, "choices": [{"delta": {
                    "content": f"w{i}"}, "index": 0}]}).encode()
                sink.write(b"data: " + frame + b"\n\n")
                sink.flush()
            time.sleep(0.0002)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--contend", action="store_true")
    args = p.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "device_kind": dev.device_kind}
    sets = operand_sets(np.random.default_rng(30))
    for contended in (False, True) if args.contend else (False,):
        stop = threading.Event()
        if contended:
            threading.Thread(target=event_loop_stand_in, args=(stop,),
                             daemon=True).start()
        for set_name, ops in sets.items():
            want = float(jax.jit(consume)(*ops))
            for form, (stage, call, buffers) in forms(ops).items():
                got = float(call(stage()))
                reading = time_form(stage, call, args.steps, 20)
                print(json.dumps({
                    "set": set_name, "form": form, "contended": contended,
                    "buffers": buffers, "same_result": got == want,
                    **{k: round(v, 4) for k, v in reading.items()},
                    "device": device}), flush=True)
        stop.set()


if __name__ == "__main__":
    main()
