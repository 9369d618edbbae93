"""Compiles for the chip, without one (the TPU compiler is installed here
and compiles for a DESCRIBED v5e): what interpret mode and the CPU cannot
show. All such tests live in THIS file, and the topology is described
inside a fixture, never at import: one process at a time may load the
TPU's library, and every xdist worker imports every test file.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.ops import moe


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# OLMoE's published widths, three layers of expert weights
OLMOE = ModelConfig(name="olmoe", hidden_size=2048, intermediate_size=1024,
                    num_layers=3, num_experts=64, num_experts_per_tok=8,
                    norm_topk_prob=False)
_MOVES = re.compile(r"=\s*bf16\[64,(?:2048,1024|1024,2048)\]\S*\s+"
                    r"(copy|fusion|dynamic-slice|transpose)\(")


def _expert_layers_hlo(one_chip, in_place: bool) -> str:
    """Optimised HLO of a scan over OLMOE's expert layers on a [32, 16]
    step, the way forward() runs them (`in_place`), or with every layer's
    expert leaves sliced out of the stack by the scan, as before PR 27's
    second chip call."""
    cfg = OLMOE
    l, d, f, e = cfg.num_layers, 2048, 1024, 64

    def run(x, layers):
        scan_layers, stacks = llama.split_expert_stacks(layers, cfg, None)
        if not in_place:
            scan_layers, stacks = layers, None

        def body(x, xs):
            lp, lid = xs
            out, stats = llama._mlp_block(x, lp, cfg, None, None, stacks,
                                          lid)
            return x + out, stats["moe_routed"]
        return jax.lax.scan(body, x, (scan_layers,
                                      jnp.arange(l, dtype=jnp.int32)))

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    layers = {"router": arr(l, d, e), "w_gate": arr(l, e, d, f),
              "w_up": arr(l, e, d, f), "w_down": arr(l, e, f, d)}
    return jax.jit(run).lower(arr(32, 16, d), layers).compile().as_text()


def test_olmoe_expert_layers_compile_for_a_v5e_and_read_the_stack_in_place(
        one_chip, monkeypatch):
    """The grouped-matmul kernel at the published widths is taken by the
    chip's compiler (three custom calls a layer), and no op copies, slices
    or re-lays-out a layer's expert leaf (268 MB) on the way to it; the
    same scan with the leaves sliced per layer is caught doing so."""
    # code that asks jax.default_backend() sees the CPU: take the chip's
    monkeypatch.setattr(moe, "grouped_matmul_impl", lambda: "gmm")
    hlo = _expert_layers_hlo(one_chip, in_place=True)
    assert hlo.count("tpu_custom_call") >= 3
    assert _MOVES.findall(hlo) == []
    sliced = _expert_layers_hlo(one_chip, in_place=False)
    assert _MOVES.findall(sliced) != []


# ling-3.0-flash-vl's served state leaf: 7 linear layers, 64 + 3 slots and
# the scratch slot, 32 heads of 128 x 128 float32; 64 decode rows
_STATE = (7, 68, 32, 128, 128)
_STATE_MOVES = re.compile(
    r"=\s*f32\[(?:7,68|64),32,128,128\]\S*\s+"
    r"(copy|fusion|gather|scatter|dynamic-slice|dynamic-update-slice)\(")


def _state_layers_hlo(one_chip, in_place: bool) -> str:
    """Optimised HLO of a scan over the linear layers of a decode step
    that carries the state leaf, as `decode_forward` does
    (tools/linattn_step_bench.py's program): each layer's one-token
    update by the slot-addressed kernel (`in_place`), or by `kda_step` on
    states gathered by slot and scattered back."""
    from dynamo_tpu.ops import linear_attention as la
    from tools.linattn_step_bench import gather_form, layers_of

    def arr(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    l, _, h, d, _ = _STATE
    ops = (arr(l, 64, h, d), arr(l, 64, h, d), arr(l, 64, h, d),
           arr(l, 64, h, d), arr(l, 64, h))
    update = functools.partial(la.kda_step_slots, impl="pallas") \
        if in_place else gather_form
    return layers_of(update).lower(
        arr(*_STATE), arr(64, dtype=jnp.int32), ops).compile().as_text()


def test_the_state_update_compiles_for_a_v5e_and_moves_no_copy_of_the_leaf(
        one_chip):
    """The slot-addressed kernel at the served shape is taken by the
    chip's compiler, the leaf aliased through it (no op copies, gathers
    or scatters the leaf or a [rows, 32, 128, 128] slice of it); the
    gather / update / scatter form is caught doing so."""
    hlo = _state_layers_hlo(one_chip, in_place=True)
    assert hlo.count("tpu_custom_call") >= 1
    assert _STATE_MOVES.findall(hlo) == []
    assert _STATE_MOVES.findall(_state_layers_hlo(one_chip, False)) != []


# falcon-h1-34b's state leaf at 6 blocks and 68 slots (64 + 3 + scratch)
_SSM_STATE = (6, 68, 32, 128, 256)
_SSM_MOVES = re.compile(
    r"=\s*f32\[(?:6,68|64),32,128,256\]\S*\s+"
    r"(copy|fusion|gather|scatter|dynamic-slice|dynamic-update-slice)\(")


def _ssm_layers_hlo(one_chip, impl: str) -> str:
    """Optimised HLO of a scan over the six blocks' one-token state
    updates of a 64-row decode step that carries the state leaf, as
    `decode_forward` does: `ssd_step_slots` by the slot-addressed kernel
    ("pallas"), or by `ssd_step` on gathered rows ("plain")."""
    from dynamo_tpu.ops import state_space as ss

    def arr(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    l, _, h, p, n = _SSM_STATE

    def layers(leaf, slots, x, dt, a, b, c, d):
        def body(leaf, at):
            y, leaf = ss.ssd_step_slots(leaf, at, slots, x, dt, a, b, c, d,
                                        impl=impl)
            return leaf, y
        return jax.lax.scan(body, leaf, jnp.arange(l))
    return jax.jit(layers, donate_argnums=0).lower(
        arr(*_SSM_STATE), arr(64, dtype=jnp.int32), arr(64, h, p),
        arr(64, h), arr(h), arr(64, 2, n), arr(64, 2, n), arr(h)
    ).compile().as_text()


def test_the_ssd_state_update_compiles_for_a_v5e_and_moves_no_copy_of_the_leaf(
        one_chip):
    """`ops/state_space.ssd_step_slots` at falcon-h1-34b's served shape is
    taken by the chip's compiler (8 heads of [128, 256] float32 a block,
    in and out double-buffered, inside the default scoped VMEM), the leaf
    aliased through it: no op copies, gathers or scatters the leaf or a
    [64, 32, 128, 256] copy of the rows' states; the gather / update /
    scatter form is caught doing so."""
    hlo = _ssm_layers_hlo(one_chip, "pallas")
    assert hlo.count("tpu_custom_call") >= 1 and "ssd_step_slots" in hlo
    assert _SSM_MOVES.findall(hlo) == []
    assert _SSM_MOVES.findall(_ssm_layers_hlo(one_chip, "plain")) != []


# brumby-14b's matrix leaf at 8 layers and 18 slots (16 + 1 + scratch)
_RET_STATE = (8, 18, 8, 128, 8320)
_RET_MOVES = re.compile(
    r"=\s*f32\[(?:8,18|16),8,128,8320\]\S*\s+"
    r"(copy|fusion|gather|scatter|dynamic-slice|dynamic-update-slice)\(")


def _retention_layers_hlo(one_chip, impl: str) -> str:
    """Optimised HLO of a scan over the eight layers' one-token state
    updates of a 16-row decode step that carries both state leaves, as
    `decode_forward` does: `retention_step_slots` by the slot-addressed
    kernel ("pallas"), or by `retention_step` on gathered rows
    ("plain")."""
    from dynamo_tpu.ops import power_retention as pr

    def arr(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    l, s, hkv, d, f = _RET_STATE

    def layers(ret_s, ret_z, slots, q, k, v, log_g):
        def body(carry, at):
            o, ret_s, ret_z = pr.retention_step_slots(
                *carry, at, slots, q, k, v, log_g, impl=impl)
            return (ret_s, ret_z), o
        return jax.lax.scan(body, (ret_s, ret_z), jnp.arange(l))
    return jax.jit(layers, donate_argnums=(0, 1)).lower(
        arr(*_RET_STATE), arr(l, s, hkv, f), arr(16, dtype=jnp.int32),
        arr(16, 40, d), arr(16, hkv, d), arr(16, hkv, d), arr(16, hkv)
    ).compile().as_text()


def test_the_retention_state_update_compiles_for_a_v5e_and_moves_no_copy(
        one_chip):
    """`ops/power_retention.retention_step_slots` at brumby-14b's served
    shape is taken by the chip's compiler (a block of 2 heads x 1664
    features of [128, F] float32, in and out double-buffered, under the
    VMEM limit the call names), the 4.9 GB leaf aliased through it: no op
    copies, gathers or scatters the leaf or a [16, 8, 128, 8320] copy of
    the rows' states (545 MB a layer, which is what does not fit beside
    it); the gather / update / scatter form is caught doing so."""
    hlo = _retention_layers_hlo(one_chip, "pallas")
    assert hlo.count("tpu_custom_call") >= 1
    assert "retention_step_slots" in hlo
    assert _RET_MOVES.findall(hlo) == []
    assert _RET_MOVES.findall(_retention_layers_hlo(one_chip, "plain")) != []


# a [64, 64] mixed step of ling-3.0-flash-vl: 3 chunk rows beside 60 decode
# rows, 4096 cells of which 252 hold a token
_GRID_WIDE = re.compile(r"=\s*f32\[(?:64,64|4096),(?:12288|32,128)\]\S*\s+"
                        r"(?!parameter|get-tuple-element|tuple|bitcast|"
                        r"while|scatter|broadcast)([\w\-]+)\(")


def _grid_wide_ops(hlo: str) -> list:
    """Ops that MAKE a float32 array of the grid's cells at the layer's
    widths; a fused in-place scatter (the scratch's rows written) is not
    one."""
    return [m.group(1) for line in hlo.splitlines()
            for m in [_GRID_WIDE.search(line)]
            if m and '/scatter"' not in line]


_LEAF_MOVES = re.compile(
    r"=\s*f32\[7,68,32,128,128\]\S*\s+(copy|copy-start|gather|"
    r"dynamic-slice)\(")


def _mixed_layers_hlo(one_chip, form: str) -> str:
    """Optimised HLO of the seven linear layers' mix of that step at the
    published widths (tools/linattn_step_bench.py's --mixed program: the
    leaves carried through a scan as `forward` carries them), over the
    step's rows (`llama.kda_mix_rows`) or over its grid as until PR 37."""
    from tools import linattn_step_bench as bench

    cfg, layers, slots_n = bench.LING, _STATE[0], _STATE[1]
    plan = bench.mixed_plan(64, 64, 3, slots_n)
    assert plan["fits"] and plan["width"] == 256
    kda_s, kda_conv, x, lp = jax.eval_shape(
        lambda: bench.mixed_operands(cfg, plan, layers, slots_n))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
    return bench.mixed_layers_of(form, cfg, plan).lower(
        *jax.tree.map(on_chip, (kda_s, kda_conv, x, lp))
    ).compile().as_text()


def test_a_mixed_steps_linear_layers_compile_for_a_v5e_over_its_rows(
        one_chip, monkeypatch):
    """The row form at the served shape is taken by the chip's compiler
    with the slot-addressed kernel in it; no op makes a float32 array of
    the grid's 64 x 64 cells (or its 4096 token rows) at the layer's
    widths but the in-place writes of the one scratch that carries `o`,
    and nothing copies, gathers or slices the state leaf whole. The grid
    form is caught making them."""
    from dynamo_tpu.ops import linear_attention as la
    monkeypatch.setattr(la, "kda_step_slots_impl", lambda: "pallas")
    hlo = _mixed_layers_hlo(one_chip, "rows")
    assert hlo.count("tpu_custom_call") >= 1
    assert _grid_wide_ops(hlo) == []
    assert _LEAF_MOVES.findall(hlo) == []
    assert _grid_wide_ops(_mixed_layers_hlo(one_chip, "grid")) != []


def _attention_hlo(one_chip, form: str) -> str:
    """Optimised HLO of one layer's attention of Moonlight's usual
    [8, 64] mixed step (seven decode rows beside one 64-token chunk,
    4096 keys a row of the one 576-wide latent leaf, 16 heads) on
    gathered keys: over the grid (`attend`) or over the step's rows
    (`attention_rows` on its 256 flat rows), tools/
    attention_rows_bench.py's two programs."""
    from dynamo_tpu.ops import attention as attn
    rows, chunk, heads, hd, keys = 8, 64, 16, 576, 4096

    def arr(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    k, lens = arr((1, rows, keys, hd)), arr((rows,), jnp.int32)
    pos, valid = arr((rows, chunk), jnp.int32), arr((rows, chunk), jnp.bool_)
    if form == "grid":
        return jax.jit(lambda q, k, lens, pos: attn.attend(
            q, k, None, lens, pos)).lower(
            arr((rows, chunk, heads, hd)), k, lens, pos).compile().as_text()
    return jax.jit(lambda q, k, lens, pos, valid, start: attn.attention_rows(
        q, k, None, lens, pos, attn.step_rows(valid, start), valid)).lower(
        arr((256, heads, hd)), k, lens, pos, valid, lens).compile().as_text()


def _largest_f32(hlo: str) -> int:
    """Elements of the largest float32 array an op of `hlo` makes."""
    sizes = [functools.reduce(lambda a, b: a * int(b), dims.split(","), 1)
             for dims in re.findall(r"=\s*f32\[([0-9,]+)\]", hlo)]
    return max(sizes)


def test_a_mixed_steps_attention_compiles_for_a_v5e_over_its_rows(one_chip):
    """The row form at a served shape is taken by the chip's compiler
    and makes no float32 array as large as the grid's scores (8 x 16 x
    64 x 4096: 134 MB a tensor a layer): its largest is the upcast of the
    gathered keys, which the grid form makes too, and its chunk row's
    scores are an eighth of the grid's. The grid form is caught making
    them."""
    scores = 8 * 16 * 64 * 4096
    rows_hlo = _attention_hlo(one_chip, "rows")
    assert "while" in rows_hlo
    assert _largest_f32(rows_hlo) == 8 * 4096 * 576 < scores
    assert _largest_f32(_attention_hlo(one_chip, "grid")) >= scores
