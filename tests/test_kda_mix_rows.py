"""A split step's linear layers over the step's ROWS (`llama.kda_mix_rows`)
against the grid form (`llama.kda_mix`: every row through the chunkwise
form on the [B, T] grid, the definition), in both layouts a step's token
rows have: the grid, and a compact step's flat rows.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.models import llama
from dynamo_tpu.ops import attention
from tests.test_ling import TINY, TOL, readings, reference_logits
from tests.test_olmoe import ENGINE_KW, Recorder

B, TQ, SLOTS, LK = 20, 16, 24, 1
N = B * TQ
# chunk rows' lengths by how many groups of KDA_GROUP_ROWS they make; the
# first continues a sequence, the second starts one over a used slot
CHUNKS = {0: (), 1: (16, 7, 2), 2: (16, 9, 5, 3, 2, 12),
          "no-fit": (16,) * 8}


def _layer():
    """One linear layer's leaves, and a state of two layers' slots that
    other sequences left full."""
    params = llama.init_params(jax.random.PRNGKey(3), TINY)
    run = next(r for r in llama.layer_runs(TINY) if r.kind == "kda")
    lp = jax.tree.map(lambda a: a[0], params[run.key])
    rng = np.random.default_rng(11)
    h, d = TINY.num_heads, TINY.linear_head_dim
    kda_s = jnp.asarray(rng.normal(size=(2, SLOTS + 1, h, d, d)),
                        jnp.float32)
    kda_conv = jnp.asarray(rng.normal(size=(2, SLOTS + 1, 3, 3 * h * d)),
                           jnp.float32)
    return lp, (kda_s, kda_conv)


def _plan(chunks, seed=0):
    """A [B, TQ] step: the chunk rows first, then one-token rows (one of
    them fresh, one without a slot), a chunk row without a slot, and
    padding rows. -> (valid [B, TQ], slots [B], fresh [B], x [B, TQ, D])."""
    rng = np.random.default_rng(seed)
    ones = B - len(chunks) - 4
    lens = list(chunks) + [1] * ones + [5] + [0] * 3
    valid = np.arange(TQ)[None, :] < np.asarray(lens)[:, None]
    slots = rng.permutation(SLOTS)[:B].astype(np.int32)
    slots[np.asarray(lens) == 0] = -1
    slots[len(chunks) + ones - 1] = -1  # a one-token row without a slot
    slots[len(chunks) + ones] = -1      # a chunk row without one
    fresh = np.zeros(B, bool)
    fresh[len(chunks)] = True           # a one-token row that starts
    if len(chunks) > 1:
        fresh[1] = True                 # a chunk row that starts
    x = rng.normal(size=(B, TQ, TINY.hidden_size)).astype(np.float32)
    return valid, slots, fresh, x


def _grid_form(lp, state, valid, slots, fresh, x):
    pre, g, beta = llama._kda_front(jnp.asarray(x), lp, TINY)
    return llama.kda_mix(state, LK, jnp.asarray(slots), lp, TINY, pre, g,
                         beta, jnp.asarray(valid), jnp.asarray(fresh))


def _rows_form(lp, state, valid, slots, fresh, x, layout, group=None):
    """-> (state', o [B, TQ, H, d] read back from the token rows). In the
    flat layout the real tokens lead the token rows in row-major order
    and every other row holds NaN: a row that is read is seen."""
    h, d = TINY.num_heads, TINY.linear_head_dim
    cells = np.flatnonzero(valid.reshape(-1))
    first = np.arange(B) * TQ
    if layout == "flat":
        at = np.cumsum(valid.reshape(-1)) - 1           # cell -> flat row
        rows = np.full((N, x.shape[-1]), np.nan, np.float32)
        rows[:cells.size] = x.reshape(N, -1)[cells]
        start = np.where(valid[:, 0], at[first], 0)
    else:
        at = np.arange(N)
        rows, start = x.reshape(N, -1), first
    kw = {} if group is None else {"group": group}
    plan = llama.step_rows(jnp.asarray(valid), jnp.asarray(start))
    # what no layer wrote must reach no result
    scratch = jnp.full((N, h, d), np.nan, jnp.float32)
    kda_s, kda_conv, o = jax.jit(functools.partial(
        llama.kda_mix_rows, cfg=TINY, **kw))(
        state + (scratch,), LK, jnp.asarray(slots), lp, x=jnp.asarray(rows),
        rows=plan, valid=jnp.asarray(valid), fresh=jnp.asarray(fresh))
    o = np.asarray(o)[at].reshape(B, TQ, h, d)
    return (kda_s, kda_conv), o


@pytest.mark.parametrize("layout", ["flat", "grid"])
@pytest.mark.parametrize("groups", [0, 1, 2, "no-fit"])
def test_the_rows_form_is_the_grid_form(groups, layout):
    """One-token rows (continued, fresh, without a slot), chunk rows of
    every length from 2 to TQ (continued, fresh over a used slot, without
    a slot) in 0, 1 and 2 groups, and padding rows: `o` at every real
    token of a row with a slot, and every touched slot's state and tail,
    are the grid form's to float32 rounding; the slots no row names, the
    scratch slot and the other layer keep every bit."""
    lp, state = _layer()
    valid, slots, fresh, x = _plan(CHUNKS[groups])
    (want_s, want_c), want_o = _grid_form(lp, state, valid, slots, fresh, x)
    (kda_s, kda_conv), o = _rows_form(lp, state, valid, slots, fresh, x,
                                      layout)
    real = valid & (slots >= 0)[:, None]
    np.testing.assert_allclose(o[real], np.asarray(want_o)[real],
                               rtol=1e-4, atol=1e-5)
    touched = slots[valid.any(axis=1) & (slots >= 0)]
    for got, want, start in ((kda_s, want_s, state[0]),
                             (kda_conv, want_c, state[1])):
        got, want, start = map(np.asarray, (got, want, start))
        np.testing.assert_allclose(got[LK, touched], want[LK, touched],
                                   rtol=1e-4, atol=1e-5)
        assert not np.array_equal(got[LK, touched], start[LK, touched])
        others = np.setdiff1d(np.arange(SLOTS + 1), touched)
        assert SLOTS in others
        np.testing.assert_array_equal(got[LK, others], start[LK, others])
        np.testing.assert_array_equal(got[1 - LK], start[1 - LK])


@pytest.mark.parametrize("group", [2, 8])
def test_the_group_size_changes_no_row(group):
    """A group is how many chunk rows ride one `kda_chunk` call, nothing
    a row can see: 2 and 8 at a time give what the constant gives."""
    lp, state = _layer()
    plan = _plan(CHUNKS[2], seed=1)
    want_state, want_o = _rows_form(lp, state, *plan, "flat")
    got_state, got_o = _rows_form(lp, state, *plan, "flat", group=group)
    real = plan[0] & (plan[1] >= 0)[:, None]
    np.testing.assert_allclose(got_o[real], want_o[real], rtol=1e-5,
                               atol=1e-6)
    for got, want in zip(got_state, want_state):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# -- through forward(): the compact step and the one that does not fit ------

def _step_operands(chunks, had=9, rows=B, tq=TQ, seed=0):
    """A [rows, tq] mixed step of `forward(last_idx=...)`: chunk rows
    that start sequences, then decode rows at position `had`, then
    padding."""
    rng = np.random.default_rng(seed)
    ps, pages = 16, 4
    lens = list(chunks) + [1] * (rows - len(chunks) - 3) + [0] * 3
    tok = rng.integers(2, TINY.vocab_size, (rows, tq)).astype(np.int32)
    positions = np.zeros((rows, tq), np.int32)
    w = np.full((rows, tq), -1, np.int32)
    pt = np.zeros((rows, pages), np.int32)
    kv = np.zeros(rows, np.int32)
    slots = np.full(rows, -1, np.int32)
    last = np.zeros(rows, np.int32)
    for i, n in enumerate(lens):
        if not n:
            continue
        pos = 0 if n > 1 else had
        positions[i, :] = pos + n - 1
        positions[i, :n] = np.arange(pos, pos + n)
        pt[i] = np.arange(pages) + 1 + pages * i
        w[i, :n] = pt[i][positions[i, :n] // ps] * ps + positions[i, :n] % ps
        kv[i], slots[i], last[i] = pos + n, i, n - 1
    meta = dict(positions=positions, page_table=pt, kv_lens=kv,
                write_idx=w, state_slots=slots)
    return tok, meta, last


def _forward(params, cache, tok, meta, last, compact):
    @jax.jit
    def step(params, tok, cache, meta, last):
        if compact:
            return llama.forward(params, TINY, tok, cache,
                                 llama.AttnMetadata(**meta), last_idx=last)
        logits, cache = llama.forward(params, TINY, tok, cache,
                                      llama.AttnMetadata(**meta))
        return jnp.take_along_axis(logits, last[:, None, None], 1)[:, 0], \
            cache
    return step(params, jnp.asarray(tok), cache,
                jax.tree.map(jnp.asarray, meta), jnp.asarray(last))


def _caches(rows):
    rng = np.random.default_rng(2)
    cache = {**llama.init_cache(TINY, 4 * rows + 4, 16),
             **llama.init_state(TINY, rows)}
    # the decode rows continue what an earlier step left
    return {k: jnp.asarray(rng.normal(size=v.shape) * 0.3, v.dtype)
            if k.startswith("kda_") else v for k, v in cache.items()}


@pytest.mark.parametrize("chunks,fits", [
    ((16, 7, 2), True), ((16,) * 8, False)], ids=["fits", "does-not-fit"])
def test_a_compact_step_is_the_grid_step(chunks, fits):
    """forward(last_idx=...) over a [20, 16] step, whose token-wise
    layers and linear layers take the flat rows where the real tokens
    fit the width (128) and the grid where they do not, gives the grid
    program's logits at the sampled rows and leaves every state leaf as
    it does: to float32 rounding where the layout differs, and BIT FOR
    BIT in the state where the step does not fit (the same rows, the
    same arithmetic, read from the grid)."""
    tok, meta, last = _step_operands(chunks)
    width, does = attention.compact_step(meta["write_idx"])
    assert (width, bool(does)) == (128, fits)
    params = llama.init_params(jax.random.PRNGKey(0), TINY)
    want, want_cache = _forward(params, _caches(B), tok, meta, last, False)
    got, cache = _forward(params, _caches(B), tok, meta, last, True)
    live = meta["state_slots"] >= 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=2e-5)
    for name in ("kda_s", "kda_conv"):
        if fits:
            np.testing.assert_allclose(cache[name], want_cache[name],
                                       rtol=1e-4, atol=2e-5)
        else:
            np.testing.assert_array_equal(cache[name], want_cache[name])
        # padding rows and the scratch slot: nothing written
        np.testing.assert_array_equal(
            np.asarray(cache[name])[:, B - 3:],
            np.asarray(_caches(B)[name])[:, B - 3:])


# -- the shape census of the served [64, 64] step ---------------------------

def _eqns(jaxpr, inside_cond=False, grid_branch=False):
    """Every equation under `jaxpr` with whether it sits inside a `cond`
    at all, and inside a `cond`'s FIRST branch (index 0 is the branch of
    a false predicate: `fits` false, the grid)."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_cond, grid_branch
        for name, sub in eqn.params.items():
            subs = sub if isinstance(sub, (tuple, list)) else (sub,)
            for i, s in enumerate(subs):
                inner = getattr(s, "jaxpr", s)
                if hasattr(inner, "eqns"):
                    cond = eqn.primitive.name == "cond"
                    yield from _eqns(
                        inner, inside_cond or cond,
                        grid_branch or (cond and name == "branches"
                                        and i == 0))


def test_a_served_mixed_step_forms_no_grid_of_the_layers_width():
    """The jaxpr of the [64, 64] mixed step that the Ling cell serves
    (tiny widths): outside the grid branches of its `cond`s (the step
    that does not fit) no float32 array has the grid's [B, Tq] or B * Tq
    rows at the linear layer's widths (3 H d, or H x d other than the
    scratch that carries o from a layer's mix to its back half, which is
    made once a program and written by row), and no `cond` takes or
    makes a state leaf: the slot-addressed update, the groups' loop and
    every state write are traced once, outside."""
    rows, tq = 64, 64
    tok, meta, last = _step_operands((64, 30, 2), rows=rows, tq=tq)
    assert bool(attention.compact_step(meta["write_idx"])[1])
    params = llama.init_params(jax.random.PRNGKey(0), TINY)
    cache = {**llama.init_cache(TINY, 4 * rows + 4, 16),
             **llama.init_state(TINY, rows)}
    jaxpr = jax.make_jaxpr(lambda p, t, c, m, l: llama.forward(
        p, TINY, t, c, llama.AttnMetadata(**m), last_idx=l))(
        params, jnp.asarray(tok), cache, jax.tree.map(jnp.asarray, meta),
        jnp.asarray(last))
    h, d = TINY.num_heads, TINY.linear_head_dim
    n = rows * tq
    # (H * d flat is the hidden size here: x's own shape, not listed)
    wide = {(rows, tq, 3 * h * d), (n, 3 * h * d), (1, n, 3 * h * d),
            (rows, tq, h, d), (1, n, h, d)}
    state = {tuple(v.shape) for k, v in cache.items()
             if k.startswith("kda_")}
    scratch_writers, conds, updates, on_the_grid = set(), 0, 0, 0
    for eqn, inside_cond, grid_branch in _eqns(jaxpr.jaxpr):
        conds += eqn.primitive.name == "cond"
        updates += eqn.primitive.name == "pallas_call" or (
            eqn.primitive.name == "scatter" and tuple(
                eqn.outvars[0].aval.shape) in state)
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            shape = tuple(getattr(aval, "shape", ()))
            if inside_cond:
                assert shape not in state, (eqn.primitive, shape)
            if getattr(aval, "dtype", None) != jnp.float32:
                continue
            if grid_branch:
                on_the_grid += shape in wide
                continue
            assert shape not in wide, (eqn.primitive, shape)
        for v in eqn.outvars:
            if tuple(v.aval.shape) == (n, h, d) and not grid_branch:
                scratch_writers.add(eqn.primitive.name)
    # (the walk sees what it looks for: the grid branch of a layer's back
    # half does take o as [B, Tq, H, d])
    assert conds and updates and on_the_grid
    # the scratch: made once, carried by the scans and the groups' loop,
    # written by row
    assert scratch_writers <= {"broadcast_in_dim", "scatter", "scan",
                               "while", "pjit"}, scratch_writers


# -- served: the engine's mixed steps, and the counter that says so --------

def test_served_split_steps_run_over_flat_rows_and_are_counted(monkeypatch):
    """End to end through the engine: nine streams decode while a
    two-chunk prompt and a short one are admitted, so the mixed steps are
    [16, 16] plans that split their rows and fit their flat width (128).
    Every served logit is the plain reference's, and
    `linattn_flat_steps_total` counts exactly the `_engine_step`s whose
    plan splits and fits."""
    rec = Recorder(monkeypatch)
    eng = NativeEngine(TINY, EngineConfig(**dict(
        ENGINE_KW, max_slots=12, num_pages=128, max_prefill_chunk=16,
        prefill_buckets=(16,))), seed=0)
    rng = np.random.default_rng(4)
    sizes = [(6, 10)] * 9 + [(28, 4), (7, 4)]
    prompts = [rng.integers(2, TINY.vocab_size, n).tolist()
               for n, _ in sizes]
    plans, stage = [], eng._stage_step

    def spy(plan, reqs, mixed=False):
        plans.append(plan.write_idx.copy())
        return stage(plan, reqs, mixed)
    monkeypatch.setattr(eng, "_stage_step", spy)
    before = eng.ledger.stats.linattn_flat_steps_total
    got, done, late = {}, set(), [9, 10]

    def add(i):
        got[f"r{i}"] = []
        eng.add_request(EngineRequest(f"r{i}", prompts[i], SamplingParams(
            max_tokens=sizes[i][1], temperature=0.0, ignore_eos=True)))
    for i in range(9):
        add(i)
    for _ in range(400):
        for ev in eng.step():
            if ev.token is not None:
                got[ev.request_id].append(ev.token)
            if ev.finished:
                done.add(ev.request_id)
        if late and all(len(got[f"r{i}"]) >= 2 for i in range(9)) \
                and (late[0] == 9 or got["r9"]):
            add(late.pop(0))
        if len(done) == len(sizes):
            break
    assert len(done) == len(sizes), sorted(done)
    seqs = [p + got[f"r{i}"] for i, p in enumerate(prompts)]
    largest, median, _ = readings(
        rec.entries, seqs,
        reference_logits(jax.device_get(eng.params), seqs), strays=True)
    assert largest < TOL[0] and median < TOL[1], (largest, median)
    flat = [w for w in plans if llama.kda_mix_splits(*w.shape)
            and attention.compact_step(w) is not None
            and attention.compact_step(w)[1]]
    assert flat and all(w.shape == (16, 16) for w in flat)
    assert eng.ledger.stats.linattn_flat_steps_total - before == len(flat)
