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

    chiprun -- python3 tools/linattn_step_bench.py --mixed 64x64 \
        --chunk-rows 1,2,3 --group 2,4,8
    python3 tools/linattn_step_bench.py --mixed 16x16 --rehearsal

`--mixed ROWSxTQ`: the seven layers' MIX of a mixed step alone, from the
step's token rows to each real token's `o` (front half, convolution, q | k
| v, the state update; no back half, no experts), for a plan of
`--chunk-rows` N full chunk rows beside ROWS - N - 1 decode rows and one row
of padding, the leaves carried through a scan over the layers as
`models/llama.forward` carries them:
  grid     the form until PR 37: the front half over the flat rows, its
           three outputs spread to the [ROWS, TQ] grid, convolution, split,
           norms and masks over every cell, the one-token rows by
           `kda_step_slots`, the chunk rows gathered 8 at a time, `o`
           gathered back (`grid_mix`, kept here as the baseline),
  rowsG    `models/llama.kda_mix_rows` with groups of G chunk rows.
A plan whose real tokens pass the flat width (16x256 with three chunk
rows) is read from the grid's rows by both forms, as `forward` does. It is
what chose `llama.KDA_GROUP_ROWS` and what PERF.md section 6, PR 37 quotes.
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

from dynamo_tpu.engine.config import ModelConfig   # noqa: E402
from dynamo_tpu.models import llama   # noqa: E402
from dynamo_tpu.ops import attention   # noqa: E402
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


# ling-3.0-flash-vl's linear layers at their published widths, and tiny
LING = ModelConfig(name="ling-kda", hidden_size=2560, num_heads=32,
                   linear_head_dim=128, linear_group_size=6,
                   dtype="bfloat16")
TINY = ModelConfig(name="tiny-kda", hidden_size=64, num_heads=4,
                   linear_head_dim=16, linear_group_size=6, dtype="float32")


def grid_mix(state, lk, slots, lp, cfg, pre, g, beta, valid, fresh):
    """`models/llama.kda_mix`'s split branch as PR 34 left it: everything
    between the front half and `_kda_out` over the [B, T] grid."""
    rows8 = 8
    kda_s, kda_conv = state
    b, tq = valid.shape
    n_slots = kda_s.shape[1]
    at = llama._slot_index(slots, n_slots)
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    keep = ~fresh
    tail = kda_conv.at[lk, at].get(mode="clip")
    tail = jnp.where(keep[:, None, None], tail, 0)
    y, tail = la.conv_with_tail(pre, tail, lp["kda_conv_w"], n_valid)
    q, k, v = llama._kda_qkv(y, cfg)
    kda_conv = kda_conv.at[lk, at].set(tail.astype(kda_conv.dtype),
                                       mode="drop")
    m = valid[:, :, None, None]
    q, k, v, g = (jnp.where(m, a, 0.0) for a in (q, k, v, g))
    beta = jnp.where(valid[:, :, None], beta, 0.0)
    o0, kda_s = la.kda_step_slots(
        kda_s, lk, jnp.where(n_valid == 1, slots, -1), q[:, 0], k[:, 0],
        v[:, 0], g[:, 0], beta[:, 0], fresh)
    o = jnp.zeros((b, tq) + o0.shape[1:], o0.dtype).at[:, 0].set(o0)
    order = jnp.pad(jnp.argsort(-n_valid).astype(jnp.int32),
                    (0, -b % rows8), constant_values=b)
    long_row = jnp.where(n_valid > 1, jnp.arange(b), b)
    long_at = jnp.where(n_valid > 1, at, n_slots)

    def group(j, carry):
        o, kda_s = carry
        rows = jax.lax.dynamic_slice_in_dim(order, j * rows8, rows8)
        at_g = long_at.at[rows].get(mode="fill", fill_value=n_slots)
        s_g = jnp.where(
            keep.at[rows].get(mode="clip")[:, None, None, None],
            kda_s.at[lk, at_g].get(mode="clip"), 0.0)
        o_g, s_g = la.kda_chunk(*(a.at[rows].get(mode="clip")
                                  for a in (q, k, v, g, beta)), s_g)
        return (o.at[long_row.at[rows].get(mode="fill", fill_value=b)].set(
                    o_g, mode="drop"),
                kda_s.at[lk, at_g].set(s_g, mode="drop"))

    n_long = jnp.sum(n_valid > 1).astype(jnp.int32)
    o, kda_s = jax.lax.fori_loop(0, -(-n_long // rows8), group, (o, kda_s))
    return (kda_s, kda_conv), o


def mixed_plan(rows: int, tq: int, chunk_rows: int, slots_n: int, seed=0):
    """`chunk_rows` full chunk rows that continue sequences, one row of
    padding, decode rows for the rest -> dict of NumPy arrays: valid,
    slots, fresh, and the layout forward() would take (`width`, `fits`,
    `cells`: the flat rows' cells, `slot`: a cell's flat row)."""
    rng = np.random.default_rng(seed)
    lens = np.asarray([tq] * chunk_rows + [1] * (rows - chunk_rows - 1)
                      + [0])
    valid = np.arange(tq)[None, :] < lens[:, None]
    slots = rng.permutation(slots_n - 1)[:rows].astype(np.int32)
    slots[lens == 0] = -1
    write_idx = np.where(valid, 1, -1)
    compact = attention.compact_step(write_idx)
    width, fits = compact if compact is not None else (rows * tq, False)
    cells = np.flatnonzero(valid.reshape(-1))
    slot = np.where(valid.reshape(-1), np.cumsum(valid.reshape(-1)) - 1, 0)
    return dict(valid=valid, slots=slots, fresh=np.zeros(rows, bool),
                width=int(width), fits=bool(fits), cells=cells, slot=slot)


def mixed_layers_of(form: str, cfg: ModelConfig, plan: dict, group: int = 0):
    """All linear layers' mix of one mixed step in one program: run(kda_s,
    kda_conv, x [rows * tq, D] token rows in the plan's layout, layers
    (stacked leaves)) -> (kda_s, kda_conv, o [L, real tokens, H, d]). The
    state leaves are carried and donated."""
    valid, slots, fresh = (jnp.asarray(plan[k])
                           for k in ("valid", "slots", "fresh"))
    b, tq = plan["valid"].shape
    n, width, fits = b * tq, plan["width"], plan["fits"]
    cells = jnp.asarray(plan["cells"], jnp.int32)
    slot = jnp.asarray(plan["slot"], jnp.int32)
    first = jnp.arange(b, dtype=jnp.int32) * tq
    # where a real token's row is, in the layout x has
    real = slot[cells] if fits else cells
    h, d = cfg.num_heads, cfg.linear_head_dim

    def run(kda_s, kda_conv, x, layers):
        rows = llama.step_rows(valid, slot[first] if fits else first)

        def layer(carry, xs):
            kda_s, kda_conv, o = carry
            lk, lp = xs
            if form == "grid":
                if fits:
                    pre, g, beta = (
                        jnp.take(a[0], slot, axis=0, mode="clip").reshape(
                            (b, tq) + a.shape[2:])
                        for a in llama._kda_front(x[None, :width], lp, cfg))
                else:
                    pre, g, beta = llama._kda_front(
                        x.reshape(b, tq, -1), lp, cfg)
                (kda_s, kda_conv), o = grid_mix(
                    (kda_s, kda_conv), lk, slots, lp, cfg, pre, g, beta,
                    valid, fresh)
                o = o.reshape((n,) + o.shape[2:])
                return (kda_s, kda_conv, o), o[cells]
            kda_s, kda_conv, o = llama.kda_mix_rows(
                (kda_s, kda_conv, o), lk, slots, lp, cfg, x, rows, valid,
                fresh, group or llama.KDA_GROUP_ROWS)
            return (kda_s, kda_conv, o), o[real]

        (kda_s, kda_conv, _), o = jax.lax.scan(
            layer, (kda_s, kda_conv, jnp.zeros((n, h, d), jnp.float32)),
            (jnp.arange(kda_s.shape[0], dtype=jnp.int32), layers))
        return kda_s, kda_conv, o
    return jax.jit(run, donate_argnums=(0, 1))


def mixed_operands(cfg: ModelConfig, plan: dict, layers: int, slots_n: int,
                   seed: int = 0):
    """(kda_s, kda_conv, x, stacked leaves): x holds the plan's real
    tokens where its layout puts them, zeros elsewhere."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    dt = jnp.dtype(cfg.dtype)
    l, dm, h, d = layers, cfg.hidden_size, cfg.num_heads, cfg.linear_head_dim
    f32 = jnp.float32

    def dense(key, *shape):
        return (jax.random.normal(key, shape, f32) * dm ** -0.5).astype(dt)
    # as models/llama._init_layer_stack draws a linear layer's leaves
    lp = {"attn_norm": jnp.ones((l, dm), dt),
          "kda_wqkv": dense(keys[0], l, dm, 3 * h * d),
          "kda_conv_w": (jax.random.normal(
              keys[1], (l, cfg.linear_conv_size, 3 * h * d), f32)
              * cfg.linear_conv_size ** -0.5).astype(dt),
          "kda_wf": dense(keys[2], l, dm, h * d),
          "kda_wb": dense(keys[3], l, dm, h),
          "kda_a_log": 0.3 * jax.random.normal(keys[4], (l, h), f32),
          "kda_dt_bias": -3.0 + 0.5 * jax.random.normal(
              keys[5], (l, h * d), f32)}
    rng = np.random.default_rng(seed)
    b, tq = plan["valid"].shape
    x = np.zeros((b * tq, cfg.hidden_size), np.float32)
    at = plan["slot"][plan["cells"]] if plan["fits"] else plan["cells"]
    x[at] = rng.normal(size=(len(at), cfg.hidden_size))
    ks, kc = jax.random.split(jax.random.PRNGKey(seed + 1))
    kda_s = jax.random.normal(ks, (layers, slots_n, h, d, d), f32)
    kda_conv = jax.random.normal(
        kc, (layers, slots_n, cfg.linear_conv_size - 1, 3 * h * d), dt)
    return kda_s, kda_conv, jnp.asarray(x, dt), lp


def main_mixed(args) -> int:
    rows, tq = (int(v) for v in args.mixed.lower().split("x"))
    cfg, layers, slots_n, reps = LING, 7, 68, 20
    if args.rehearsal:
        cfg, layers, slots_n, reps = TINY, 2, rows + 4, 2
    elif jax.default_backend() != "tpu":
        print("no TPU attached: --rehearsal runs here", file=sys.stderr)
        return 1
    slots_n = max(slots_n, rows + 4)
    for chunk_rows in (int(v) for v in args.chunk_rows.split(",")):
        plan = mixed_plan(rows, tq, chunk_rows, slots_n)
        kda_s0, kda_conv0, x, lp = mixed_operands(cfg, plan, layers,
                                                  slots_n)
        forms = [("grid", 0)] + [("rows", int(g))
                                 for g in args.group.split(",")]
        want = None
        for form, group in forms:
            run = mixed_layers_of(form, cfg, plan, group)
            # the leaves are donated: every form starts from a copy
            kda_s, kda_conv, o = run(jnp.copy(kda_s0), jnp.copy(kda_conv0),
                                     x, lp)
            got = (np.asarray(o), np.asarray(kda_s))
            want = want or got
            times = []
            for _ in range(reps + 3):
                t0 = time.perf_counter()
                kda_s, kda_conv, o = run(kda_s, kda_conv, x, lp)
                jax.block_until_ready((kda_s, kda_conv, o))
                times.append(time.perf_counter() - t0)
            ms = 1e3 * statistics.median(times[3:])
            print(json.dumps({
                "plan": f"{rows}x{tq}", "chunk_rows": chunk_rows,
                "real_tokens": int(plan["valid"].sum()),
                "width": plan["width"], "fits": plan["fits"],
                "form": form + (str(group) if group else ""),
                "device": jax.devices()[0].device_kind,
                "ms": round(ms, 3), "ms_a_layer": round(ms / layers, 3),
                "o_diff": float(np.abs(got[0] - want[0]).max()),
                "o_scale": float(np.abs(want[0]).max()),
                "state_diff": float(np.abs(got[1] - want[1]).max())}),
                flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shapes, the kernel in the interpreter")
    ap.add_argument("--heads-per-block", default="8,16,32")
    ap.add_argument("--mixed", default="", metavar="ROWSxTQ",
                    help="time a mixed step's mix, grid form against "
                         "row form, instead of the one-token update")
    ap.add_argument("--chunk-rows", default="1,2,3",
                    help="--mixed: chunk rows beside the decode rows")
    ap.add_argument("--group", default="2,4,8",
                    help="--mixed: chunk rows a group of the row form")
    args = ap.parse_args(argv)
    if args.mixed:
        return main_mixed(args)
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
