"""A compact step's attention over its REAL queries (ops/attention.
attention_rows, PRs 47 / 48) held to the grid form (`paged_attention`) at the
real cells of seeded plans; where `models/llama.forward` traces each
form; and the host's count of the steps that ran it."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import ModelConfig
from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention as attn
from dynamo_tpu.ops.kv_quant import quantize_rows

_ROWS, _CHUNK, _PS, _TABLE = 16, 16, 8, 6       # Lk = 48 keys a row

# (decode rows, the chunk rows' real tokens): a mixed step's usual plan
# (one chunk row, six dead rows), decode rows alone (the loop makes no
# pass), two and three chunk rows, EVERY row a chunk row (more such rows
# than any static bound would budget), a pure chunk, one-token chunks
# among the rows
_PLANS = {
    "mixed": (9, (16,)),
    "decode-only": (12, ()),
    "two-chunks": (6, (16, 9)),
    "three-chunks": (7, (16, 5, 2)),
    "all-chunk-rows": (0, (3,) * 16),
    "pure-chunk": (0, (16,)),
    "one-token-chunks": (5, (1, 1, 12)),
}
# what a model hands the gather path, each over GQA 4 query heads a kv head
_CASES = {
    "gqa": {},
    "mha": {"hkv": 4},
    "one-leaf-latent": {"hkv": 1, "one_leaf": True},
    "window": {"window": 11},
    "softcap-qscale": {"softcap": 30.0, "q_scale": 0.2},
    "int8-scales": {"int8": True},
}


def _plan(seed, n_decode, chunks):
    """A seeded [_ROWS, _CHUNK] plan: decode rows of one token at a
    drawn context, chunk rows behind a drawn cached prefix, padding rows
    last. -> (page_table, kv_lens, positions, valid)."""
    rng = np.random.default_rng(seed)
    lk = _TABLE * _PS
    kv_lens = np.zeros(_ROWS, np.int32)
    positions = np.zeros((_ROWS, _CHUNK), np.int32)
    valid = np.zeros((_ROWS, _CHUNK), bool)
    for r, n in enumerate((1,) * n_decode + tuple(chunks)):
        first = int(rng.integers(0, lk - n + 1))
        kv_lens[r] = first + n
        valid[r, :n] = True
        # padding cells repeat the last real position, as the planner's do
        positions[r] = first + np.minimum(np.arange(_CHUNK), n - 1)
    page_table = rng.permutation(_ROWS * _TABLE).astype(np.int32).reshape(
        _ROWS, _TABLE)
    return page_table, kv_lens, positions, valid


def _pool(seed, hkv, hd, one_leaf, int8):
    key = jax.random.PRNGKey(seed)
    shape = (hkv, _ROWS * _TABLE, _PS, hd)
    k = jax.random.normal(key, shape, jnp.float32)
    v = None if one_leaf else jax.random.normal(
        jax.random.fold_in(key, 1), shape, jnp.float32)
    if not int8:
        return k, v, None, None
    (k, ks), (v, vs) = quantize_rows(k), quantize_rows(v)
    return k, v, ks, vs


@pytest.mark.parametrize("layout", ["grid-rows", "flat-rows"])
@pytest.mark.parametrize("plan", sorted(_PLANS))
@pytest.mark.parametrize("case", sorted(_CASES))
def test_row_form_is_the_grid_form_at_the_real_cells(case, plan, layout):
    """`attention_rows` against `paged_attention` over the same pool and
    page tables: every real cell of the plan reads the same output, with
    the step's token rows in either layout a step has (the grid's own
    order, row r at r * chunk; a compact step's flat rows,
    `compact_index`); rows that hold no real token stay zero."""
    c = _CASES[case]
    hkv, hd, g = c.get("hkv", 2), 16, 4
    seed = sorted(_CASES).index(case) * 10 + sorted(_PLANS).index(plan)
    page_table, kv_lens, positions, valid = _plan(seed, *_PLANS[plan])
    k_cache, v_cache, ks, vs = _pool(
        seed, hkv, hd, c.get("one_leaf", False), c.get("int8", False))
    q = jax.random.normal(jax.random.PRNGKey(seed + 99),
                          (_ROWS, _CHUNK, hkv * g, hd), jnp.float32)
    window = None if "window" not in c else jnp.int32(c["window"])
    kw = dict(softcap=c.get("softcap", 0.0), window=window,
              q_scale=c.get("q_scale", 0.0))
    want = np.asarray(attn.paged_attention(
        q, k_cache, v_cache, jnp.asarray(page_table), jnp.asarray(kv_lens),
        jnp.asarray(positions), k_scale=ks, v_scale=vs, **kw))

    n = _ROWS * _CHUNK
    cells = np.flatnonzero(valid.reshape(-1))
    if layout == "grid-rows":
        rows_q, start = q.reshape((n,) + q.shape[2:]), np.arange(_ROWS) * _CHUNK
        at = cells
    else:
        # a compact step's: the real cells lead, in row-major order
        width = 128 if cells.size <= 128 else n
        order = np.concatenate([cells, np.zeros(width - cells.size, int)])
        rows_q = q.reshape((n,) + q.shape[2:])[order]
        slot = np.cumsum(valid.reshape(-1)) - 1
        start, at = slot[np.arange(_ROWS) * _CHUNK], np.arange(cells.size)
    rows = attn.step_rows(jnp.asarray(valid), jnp.asarray(start))
    k, v = attn.gather_kv(k_cache, v_cache, jnp.asarray(page_table),
                          q.dtype, ks, vs)
    got = np.asarray(jax.jit(
        lambda rq, k, v: attn.attention_rows(
            rq, k, v, jnp.asarray(kv_lens), jnp.asarray(positions), rows,
            jnp.asarray(valid), **kw))(rows_q, k, v))
    assert got.shape == rows_q.shape
    np.testing.assert_allclose(
        got[at], want.reshape((n,) + want.shape[2:])[cells],
        rtol=2e-5, atol=2e-5)
    rest = np.setdiff1d(np.arange(got.shape[0]), at)
    assert not got[rest].any()


def test_row_form_computes_no_padding_row_of_a_chunk():
    """A stale non-finite value past a row's length (a recycled page)
    reaches no output: the row form zeroes the values its mask hides, as
    the grid form does."""
    page_table, kv_lens, positions, valid = _plan(3, *_PLANS["mixed"])
    k_cache, v_cache, *_ = _pool(3, 2, 16, False, False)
    stale = np.ones((_ROWS * _TABLE, _PS), bool)
    for r in range(_ROWS):
        for j in range(kv_lens[r]):
            stale[page_table[r, j // _PS], j % _PS] = False
    v_cache = jnp.where(stale[None, :, :, None], jnp.nan, v_cache)
    q = jax.random.normal(jax.random.PRNGKey(5), (_ROWS * _CHUNK, 8, 16))
    k, v = attn.gather_kv(k_cache, v_cache, jnp.asarray(page_table),
                          q.dtype)
    assert bool(jnp.isnan(v).any())
    got = attn.attention_rows(
        q, k, v, jnp.asarray(kv_lens), jnp.asarray(positions),
        attn.step_rows(jnp.asarray(valid),
                       jnp.arange(_ROWS) * _CHUNK), jnp.asarray(valid))
    assert bool(jnp.isfinite(got).all())


CFG = ModelConfig(name="tiny-rows", vocab_size=96, hidden_size=32,
                  num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8,
                  intermediate_size=64, dtype="float32")


def _score_shapes(jaxpr, acc):
    """The shape of every float32 [B, Hkv, g, Tq, Lk] array (the scores
    and what the mask and the softmax make of them: keys last) that an
    equation of `jaxpr` or of a sub-jaxpr makes."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = var.aval
            if getattr(aval, "ndim", 0) == 5 and aval.dtype == jnp.float32 \
                    and aval.shape[-1] == _TABLE * _PS:
                acc.add(tuple(aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _score_shapes(sub, acc)
    return acc


def _back_conds(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and any(
                _score_shapes(b.jaxpr, set()) for b in eqn.params["branches"]):
            found.append(eqn)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _back_conds(sub, found)
    return found


def test_forward_traces_the_row_form_in_the_compact_branch_alone():
    """The `cond` a layer's back half already is holds both forms: its
    grid branch scores all [B, Tq] queries, its compact branch one query
    a row and, in the loop, one chunk row's own; the pool's pages are
    gathered OUTSIDE it, once (the gather is no operand-free branch
    op), and a step without a `cond` (a shape whose grid is no larger
    than its width) is the grid form alone, as is a step of a shape
    where the row form does not pay (`attention_rows_pay`)."""
    lk, g = _TABLE * _PS, CFG.num_heads // CFG.num_kv_heads

    def trace(rows, chunk, cfg=CFG):
        def arr(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32)
        params = jax.eval_shape(
            lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
        pool = jax.eval_shape(
            lambda: llama.init_cache(cfg, rows * _TABLE, _PS))
        return jax.make_jaxpr(
            lambda params, pool, tokens, last, *meta: llama.forward(
                params, cfg, tokens, pool, llama.AttnMetadata(*meta),
                last_idx=last))(
            params, pool, arr(rows, chunk), arr(rows), arr(rows, chunk),
            arr(rows, _TABLE), arr(rows), arr(rows, chunk)).jaxpr

    conds = _back_conds(trace(_ROWS, _CHUNK), [])
    assert len(conds) == 1          # one layer body, one back half
    grid, compact = (_score_shapes(b.jaxpr, set())
                     for b in conds[0].params["branches"])
    assert grid == {(_ROWS, CFG.num_kv_heads, g, _CHUNK, lk)}
    assert compact == {(_ROWS, CFG.num_kv_heads, g, 1, lk),
                       (1, CFG.num_kv_heads, g, _CHUNK, lk)}
    # a model whose row form does not pay at [16, 16]: the grid form,
    # once, outside every `cond`
    wide = trace(_ROWS, _CHUNK, WIDE_HEADS)
    assert not _back_conds(wide, [])
    assert _score_shapes(wide, set()) == {
        (_ROWS, CFG.num_kv_heads, g, _CHUNK, lk)}
    # [4, 16]: 64 cells under a flat width of 128, no `cond`
    small = trace(4, _CHUNK)
    assert not _back_conds(small, [])
    assert _score_shapes(small, set()) == {
        (4, CFG.num_kv_heads, g, _CHUNK, lk)}


# the same widths with heads of 64: a row and key gathered for a
# [16, 16] step hold 2 x 2 x 64 = 256 values, its scores 4 x 16 = 64
WIDE_HEADS = ModelConfig(name="tiny-rows-wide", vocab_size=96,
                         hidden_size=32, num_layers=2, num_heads=4,
                         num_kv_heads=2, head_dim=64, intermediate_size=64,
                         dtype="float32")


def test_the_form_follows_the_shape():
    """`attention_rows_pay`: the row form where a row and key of the
    grid's scores hold at least what was gathered for them; `forward`
    traces the grid form outside every `cond` where it does not."""
    pay = attn.attention_rows_pay
    assert pay(16, 4, 64) and not pay(16, 4, 65)
    assert llama.step_attention_rows(CFG, _CHUNK)
    assert not llama.step_attention_rows(WIDE_HEADS, _CHUNK)


# the step shapes the benchmark's cells run ([rows, chunk]: the mixed
# steps of each cell's slots, and a prompt's own [4, 128] steps), by the
# configuration files they serve
_CELL_SHAPES = [
    ("mistral-7b", 32, 16, False),          # decode-closed, chat-open
    ("mistral-7b", 8, 64, True),            # chat-open's prompts
    ("mixtral-8x7b", 32, 16, False),
    ("olmoe-1b-7b", 32, 16, False),
    ("moonlight-16b-a3b", 8, 64, True),
    ("moonlight-16b-a3b", 4, 128, True),
    ("ling-3.0-flash-vl", 64, 64, True),
    ("mellum2-12b-a2.5b", 8, 64, True),
    ("mellum2-12b-a2.5b", 4, 128, True),
    ("trinity-mini", 8, 64, True),
    ("trinity-mini", 4, 128, True),
    ("falcon-h1-34b", 64, 64, True),
]


@pytest.mark.parametrize("config,rows,chunk,pays", _CELL_SHAPES)
def test_the_rule_at_the_benchmarks_step_shapes(config, rows, chunk, pays):
    """[8, 64], [4, 128] and [64, 64] steps take the row form, [32, 16]
    ones keep the grid outside the `cond`s, for the served configurations
    at their published widths."""
    import json
    import os

    from dynamo_tpu.models.loader import config_from_hf
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs", config,
        "config.json")
    with open(path) as f:
        cfg = config_from_hf(json.load(f), name=config)
    assert llama.step_attention_rows(cfg, chunk) == pays
    # the rule needs a compact step, which these shapes all are
    assert attn.compact_step(np.full((rows, chunk), -1, np.int32)) \
        is not None


@pytest.mark.parametrize("n_decode,chunks,cfg,compact,split", [
    (9, (16,), CFG, True, True),            # a mixed step inside its width
    (0, (3,) * 16, CFG, True, True),        # sixteen chunk rows inside it
    (0, (16,) * 9, CFG, False, False),      # 144 real tokens: the grid
    (9, (16,), WIDE_HEADS, True, False),    # compact, attention on the grid
])
def test_host_counts_a_split_step_where_the_program_runs_one(
        n_decode, chunks, cfg, compact, split):
    """`llm_engine_attn_split_steps_total` from the NumPy plan, by the
    predicates the program traces (`NativeEngine._step_forms`): a compact
    step of a shape where the row form pays, whatever the number of its
    chunk rows; a compact step of another shape counts as compact alone,
    a step over its width as neither."""
    import functools

    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.observability.ledger import StepLedger

    valid = np.zeros((_ROWS, _CHUNK), bool)
    for r, n in enumerate((1,) * n_decode + tuple(chunks)):
        valid[r, :n] = True
    write_idx = np.where(valid, 5, -1).astype(np.int32)
    assert bool(llama.step_compaction(write_idx)[1]) == compact
    assert bool(jax.jit(lambda w: attn.compact_step(w)[1])(
        jnp.asarray(write_idx))) == compact
    eng = types.SimpleNamespace(pp=1, _sp_mesh=None, model_cfg=cfg)
    eng._dense_rows = functools.partial(NativeEngine._dense_rows, eng)
    forms = NativeEngine._step_forms(eng, types.SimpleNamespace(
        tokens=write_idx, write_idx=write_idx))
    assert forms["attn_rows"] == split
    ledger = StepLedger()
    ledger.stats = type(ledger.stats)()
    ledger.record_step("mixed", _ROWS, n_decode + len(chunks),
                       int(valid.sum()), valid.size, 0, 1, 0, 0, 0, 0, 0, 0,
                       **forms)
    assert ledger.stats.compact_steps_total == int(compact)
    assert ledger.stats.attn_split_steps_total == int(split)


def test_the_benchmarks_share_reads_the_two_counters():
    """`benchmark/layer_metrics/attn.split_step_share.json` over two
    scrapes: 100 x the split steps over the compact steps between them;
    on a program without the counter (the parent) nothing, and the
    result line leaves the metric out. What is held of its
    `BENCHMARK.json` entry is the EXPRESSION and what it moves, not the
    list of cells: every engine exports both counters, so a fold may
    drop the key, and every later cell then has the metric for nothing."""
    import json
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        from harness import readers
    finally:
        sys.path.remove(bench)
    spec = readers.load_metric("attn.split_step_share", bench)
    names = ("llm_engine_attn_split_steps_total",
             "llm_engine_compact_steps_total")
    before, after = dict(zip(names, (7.0, 9.0))), dict(zip(names, (57.0, 59.0)))
    assert readers.evaluate(spec["expr"], {"prom": (before, after)}) == 100.0
    after[names[0]] = 32.0
    assert readers.evaluate(spec["expr"], {"prom": (before, after)}) == 50.0
    assert readers.evaluate(spec["expr"], {"prom": (before, before)}) is None
    parent = ({names[1]: 9.0}, {names[1]: 59.0})
    assert readers.evaluate(spec["expr"], {"prom": parent}) is None
    with open(os.path.join(os.path.dirname(bench), "BENCHMARK.json")) as f:
        listed = json.load(f)
    entry, = (e for e in listed["per_layer"] if e["name"] == spec["name"])
    cells = entry.pop("workloads", None)
    assert entry == {
        "name": spec["name"], "layer": "attention", "unit": "%",
        "better": "higher", "source": "program_counter",
        "moves": "tpot_p50_ms"}
    assert cells is None or set(cells) <= {
        w["name"] for w in listed["workloads"]}
    assert spec["expr"] == {"op": "mul", "args": [{"const": 100}, {
        "op": "div", "args": [
            {"prom": "llm_engine_attn_split_steps_total"},
            {"prom": "llm_engine_compact_steps_total"}]}]}
    assert (spec["layer"], spec["unit"], spec["better"], spec["moves"]) == (
        "attention", "%", "higher", "tpot_p50_ms")
