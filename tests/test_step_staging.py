"""A step's host operands reach the device in one staging call (ISSUE 30).

- (i) for every step kind's operand set the packed buffer unpacks, inside
  a program, bit for bit to the host arrays: `write_idx` -1, float32
  through the bit-cast (NaN payloads, -0.0), bool, a padded row, a
  `[rows, 0]` operand;
- (ii) an engine driven through admissions (mixed steps, windows, an
  admission again) emits the tokens of an oracle that stages the way the
  engine did before: one array an operand, nothing packed;
- (iii) `host_buffers` a step is what PERF.md says for each step kind,
  and a chained window over an unchanged slot set stages nothing;
- (iv) no per-array staging is left on the step path (a source check).
"""
import ast
import contextlib
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dynamo_tpu.engine.engine as engine_mod
from dynamo_tpu.engine.config import EngineConfig, ModelConfig
from dynamo_tpu.engine.engine import (
    PP_WINDOW_OPERANDS, STEP_OPERANDS, VERIFY_OPERANDS, WINDOW_OPERANDS,
    NativeEngine, pack_operands, unpack_operands,
)
from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.observability.ledger import LEDGER_STATS
from dynamo_tpu.parallel.mesh import make_mesh

CFG = ModelConfig(dtype="float32", max_model_len=512)

# -- (i) pack / unpack ---------------------------------------------------------

ROWS, CHUNK, PAGES, STOPS = 6, 8, 5, 2
WEIRD_F32 = np.array([0x7fc00001, 0xffc12345, 0x80000000, 0x7f800000,
                      0x00000001, 0x3f333333], np.uint32).view(np.float32)


def _i32(rng, *shape):
    a = rng.integers(-2**31, 2**31, shape, dtype=np.int64).astype(np.int32)
    a[-1] = 0                       # the padded row
    return a


def host_operand(name: str, rng) -> np.ndarray:
    """One operand as its staging site would hand it over, with the
    values that a careless cast would lose."""
    if name in ("tokens", "positions"):
        return _i32(rng, ROWS, CHUNK)
    if name == "write_idx":
        a = _i32(rng, ROWS, CHUNK)
        a[:, CHUNK // 2:] = -1      # dropped rows
        return a
    if name in ("page_table", "base_table"):
        return _i32(rng, ROWS, PAGES)
    if name == "stop_ids":
        return np.full((ROWS, STOPS), -1, np.int32)
    if name in ("temperature", "top_p", "rep_penalty"):
        return rng.permutation(WEIRD_F32)
    if name == "ignore_eos":
        return np.arange(ROWS) % 2 == 0
    if name == "mm_mask":
        return rng.random((ROWS, CHUNK)) < 0.5
    return _i32(rng, ROWS)


OPERAND_SETS = {
    "step": STEP_OPERANDS,
    "step+penalty": STEP_OPERANDS + ("rep_penalty",),
    "step+images": STEP_OPERANDS + ("mm_mask",),
    "window": WINDOW_OPERANDS,
    "window+penalty": WINDOW_OPERANDS + ("rep_penalty",),
    "pp_window": PP_WINDOW_OPERANDS,
    "verify": VERIFY_OPERANDS,
}


@pytest.mark.parametrize("kind", OPERAND_SETS)
def test_packed_operands_unpack_bit_for_bit(kind):
    rng = np.random.default_rng(len(kind))
    host = tuple(host_operand(n, rng) for n in OPERAND_SETS[kind])
    layout, buf = pack_operands(host)
    assert buf.dtype == np.int32 and buf.shape[0] == ROWS
    assert buf.shape[1] == sum(a[0].size for a in host)
    got = jax.jit(unpack_operands, static_argnums=(0,))(
        layout, jax.device_put(buf))
    assert len(got) == len(host)
    for name, want, have in zip(OPERAND_SETS[kind], host, got):
        have = np.asarray(have)
        assert (have.dtype, have.shape) == (want.dtype, want.shape), name
        assert have.tobytes() == want.tobytes(), name


def test_the_layout_is_a_function_of_shapes_and_dtypes_alone():
    a = tuple(host_operand(n, np.random.default_rng(1))
              for n in WINDOW_OPERANDS)
    b = tuple(host_operand(n, np.random.default_rng(2))
              for n in WINDOW_OPERANDS)
    assert pack_operands(a)[0] == pack_operands(b)[0]
    assert hash(pack_operands(a)[0]) == hash(pack_operands(b)[0])


def test_an_empty_operand_and_a_strided_one_pack():
    """A window without stop ids hands over `[S, 0]`; the base table is a
    column slice of the page table, not contiguous (nor is the reversed
    float32 view here)."""
    table = _i32(np.random.default_rng(3), ROWS, PAGES)
    host = (table, table[:, :2], np.zeros((ROWS, 0), np.int32),
            WEIRD_F32[::-1])
    layout, buf = pack_operands(host)
    assert layout == (("i", PAGES), ("i", 2), ("i", 0), ("f", None))
    got = jax.jit(unpack_operands, static_argnums=(0,))(layout, buf)
    for want, have in zip(host, got):
        assert np.asarray(have).tobytes() == want.tobytes()
        assert np.asarray(have).shape == want.shape


@pytest.mark.parametrize("bad", [
    np.zeros((ROWS,), np.int64), np.zeros((ROWS,), np.float64),
    np.zeros((ROWS, 2, 2), np.int32)], ids=["int64", "float64", "rank3"])
def test_what_does_not_pack_is_refused(bad):
    with pytest.raises(TypeError, match="does not pack"):
        pack_operands((np.zeros((ROWS,), np.int32), bad))


# -- (ii) the same tokens as an oracle that stages the parent's way ------------

def make_engine(model_cfg=CFG, mesh=None, **kw):
    defaults = dict(page_size=8, num_pages=64, max_slots=4,
                    max_prefill_chunk=32, prefill_buckets=(8, 16, 32),
                    max_model_len=512, decode_steps=8)
    defaults.update(kw)
    return NativeEngine(model_cfg, EngineConfig(**defaults), mesh=mesh,
                        seed=0)


@contextlib.contextmanager
def staged_the_parents_way():
    """While open, an engine built AND driven inside stages as the engine
    did before PR 30: one `jnp.asarray` an operand and programs that take
    each operand as an argument of its own; nothing is packed."""
    real = engine_mod.pack_operands, engine_mod.unpack_operands
    engine_mod.pack_operands = lambda arrays: (
        None, tuple(jnp.asarray(a) for a in arrays))
    engine_mod.unpack_operands = lambda layout, arrays: arrays
    try:
        yield
    finally:
        engine_mod.pack_operands, engine_mod.unpack_operands = real


@contextlib.contextmanager
def always_drafting():
    """A random-weight model never repeats itself, so the real n-gram
    proposer goes silent after a token (tests/test_spec_decode.py): give
    every step a draft. At a 1-step window any draft passes the gate."""
    import dynamo_tpu.engine.spec as spec_mod
    real = spec_mod.ngram_propose
    spec_mod.ngram_propose = lambda tokens, k, *a, **kw: [7] * min(k, 2)
    try:
        yield dict(spec_decode="ngram", spec_k=4, pipeline_depth=1,
                   decode_steps=1)
    finally:
        spec_mod.ngram_propose = real


def drive(eng, arrivals: dict) -> dict:
    """Step `eng` until idle; `arrivals` maps a step index to the requests
    added before it. Tokens per request id."""
    out: dict = {}
    i = 0
    while eng.has_work() or any(k >= i for k in arrivals):
        for req in arrivals.get(i, ()):
            eng.add_request(req)
        for ev in eng.step():
            if ev.token is not None:
                out.setdefault(ev.request_id, []).append(ev.token)
        i += 1
    return out


def _params(mode: str, n: int, seed: int) -> SamplingParams:
    if mode == "greedy":
        return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)
    if mode == "sampled":
        return SamplingParams(max_tokens=n, temperature=0.7, top_p=0.95,
                              top_k=40, seed=seed, ignore_eos=seed % 2 == 0)
    assert mode == "penalty"
    return SamplingParams(max_tokens=n, temperature=0.8, seed=seed,
                          repetition_penalty=1.3, logprobs=2,
                          stop_token_ids=[5, 9])


def _arrivals(mode: str) -> dict:
    """An admission, windows, a second admission into the running batch
    (mixed steps), windows again, and a third after more windows."""
    return {
        0: [EngineRequest("a", list(range(10, 40)), _params(mode, 40, 3))],
        3: [EngineRequest("b", list(range(50, 95)), _params(mode, 30, 4)),
            EngineRequest("c", list(range(7, 19)), _params(mode, 12, 5))],
        9: [EngineRequest("d", list(range(100, 170)), _params(mode, 20, 6))],
    }


@pytest.mark.parametrize("depth", [1, 2], ids=["sync", "pipelined"])
@pytest.mark.parametrize("mode", ["greedy", "sampled", "penalty"])
def test_tokens_match_the_parents_staging(mode, depth):
    eng = make_engine(pipeline_depth=depth)
    got = drive(eng, _arrivals(mode))
    assert eng.mixed_steps > 0 and eng.decode_windows > 0
    assert eng.decode_plan_uploads >= 2        # windows staged afresh, twice
    with staged_the_parents_way():
        oracle = make_engine(pipeline_depth=depth)
        want = drive(oracle, _arrivals(mode))
        assert oracle.mixed_steps == eng.mixed_steps
    assert set(got) == {"a", "b", "c", "d"}
    assert got == want


def test_speculative_verify_matches_the_parents_staging():
    with always_drafting() as kw:
        eng = make_engine(**kw)
        got = drive(eng, _arrivals("greedy"))
        assert eng.spec_steps > 0
        with staged_the_parents_way():
            want = drive(make_engine(**kw), _arrivals("greedy"))
    assert got == want


@pytest.mark.parametrize("mode", ["greedy", "sampled"])
def test_pipeline_parallel_windows_match_the_parents_staging(mode):
    """On a pp x tp mesh the packed buffer is an uncommitted array like
    the per-operand arrays before it: the call replicates it."""
    cfg = ModelConfig(dtype="float32", num_layers=4, max_model_len=128)
    kw = dict(max_slots=2, max_prefill_chunk=16, prefill_buckets=(8, 16),
              max_model_len=128)
    arrivals = {0: [EngineRequest("a", list(range(3, 15)),
                                  _params(mode, 12, 3))],
                2: [EngineRequest("b", list(range(40, 60)),
                                  _params(mode, 10, 4))]}

    def run():
        mesh = make_mesh(pp=2, tp=2, devices=jax.devices()[:4])
        eng = make_engine(cfg, mesh, **kw)
        out = drive(eng, arrivals)
        assert eng.decode_windows > 0
        return out

    got = run()
    with staged_the_parents_way():
        want = run()
    assert got == want
    assert got == drive(make_engine(cfg, **kw), arrivals)   # one device


# -- (iii) buffers a step ------------------------------------------------------

def buffers_by_step(eng, arrivals: dict) -> list:
    """[(step kinds committed, windows dispatched, buffers staged)] a
    step() call."""
    kinds = ("prefill", "mixed", "decode", "spec")
    steps, i = [], 0
    while eng.has_work() or any(k >= i for k in arrivals):
        for req in arrivals.get(i, ()):
            eng.add_request(req)
        k0 = {k: getattr(LEDGER_STATS, "steps_" + k) for k in kinds}
        b0, w0, u0 = (eng.host_buffers, eng.decode_windows,
                      eng.decode_plan_uploads)
        eng.step()
        steps.append((
            [k for k in kinds if getattr(LEDGER_STATS, "steps_" + k) > k0[k]],
            eng.decode_windows - w0, eng.decode_plan_uploads - u0,
            eng.host_buffers - b0))
        i += 1
    return steps


@pytest.mark.parametrize("mode,step_buffers,window_buffers", [
    ("sampled", 1, 2),      # the packed plan; + the window's carry
    ("penalty", 2, 3),      # + the penalty history, a buffer of its own
])
def test_host_buffers_a_step(mode, step_buffers, window_buffers):
    eng = make_engine(pipeline_depth=1)
    total0 = LEDGER_STATS.host_buffers_total
    steps = buffers_by_step(eng, _arrivals(mode))
    kinds, fresh_windows = set(), 0
    for kind, windows, fresh, buffers in steps:
        if kind in (["prefill"], ["mixed"]):
            assert buffers == step_buffers, (kind, buffers)
        else:
            # a window stages only when it stages afresh (with a penalty
            # every one does)
            assert kind == ["decode"] and windows == 1, kind
            assert buffers == (window_buffers if fresh else 0), buffers
            fresh_windows += fresh
        kinds.update(kind)
    assert kinds == {"prefill", "mixed", "decode"} and fresh_windows >= 2
    assert eng.metrics().host_buffers == eng.host_buffers \
        == LEDGER_STATS.host_buffers_total - total0
    assert eng.host_buffers == sum(s[-1] for s in steps)


def test_a_chained_window_stages_nothing():
    """Unchanged slot set and base width: every window after the first,
    chained by the two-deep pipeline or planned anew, is fed the device's
    operands and the device's carry."""
    eng = make_engine(pipeline_depth=2, page_size=64, num_pages=16,
                      max_prefill_chunk=32)
    steps = buffers_by_step(eng, {0: [EngineRequest(
        "a", list(range(10, 40)), _params("sampled", 33, 3))]})
    windows = [s for s in steps if s[1]]
    assert len(windows) >= 4        # 62 tokens of context: one base page
    assert [s[-1] for s in windows] == [2] + [0] * (len(windows) - 1)
    assert eng.decode_plan_uploads == 1
    assert eng.host_buffers == 1 + 2            # the prefill, one window


def test_a_verify_step_stages_one_buffer():
    with always_drafting() as kw:
        steps = buffers_by_step(make_engine(**kw), {0: [EngineRequest(
            "s", [11, 12, 13, 14] * 4, _params("greedy", 8, 1))]})
    spec = [s for s in steps if s[0] == ["spec"]]
    assert spec and all(s[-1] == 1 for s in spec)


def test_staged_operands_live_on_the_device():
    """Whatever a window keeps for its chained follow-ups is a device
    array, never NumPy handed over again at every call."""
    eng = make_engine(pipeline_depth=2)
    eng.add_request(EngineRequest("a", list(range(10, 40)),
                                  _params("sampled", 30, 3)))
    while eng._dec_state is None:
        eng.step()
    layout, packed = eng._dec_state["dev"]
    assert isinstance(layout, tuple) and hash(layout) is not None
    assert isinstance(packed, jax.Array) and packed.dtype == jnp.int32
    assert isinstance(eng._dec_state["next"], jax.Array)
    assert eng._dec_state["next"].shape == (4, 3)
    while eng.has_work():
        eng.step()


# -- (iv) nothing stages an operand of its own on the step path ----------------

STEP_PATH = ("_stage_", "_run_", "_launch_", "_dispatch_", "_prime_",
             "_pipeline_", "_fetch_", "_window_")
PUTS = {("jnp", "asarray"), ("jnp", "array"), ("jax", "device_put")}


def _puts(fn: ast.FunctionDef) -> list:
    return [(n.func.value.id, n.func.attr) for n in ast.walk(fn)
            if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute)
            and isinstance(n.func.value, ast.Name)
            and (n.func.value.id, n.func.attr) in PUTS]


def test_no_per_array_staging_is_left_on_the_step_path():
    tree = ast.parse(inspect.getsource(NativeEngine).lstrip())
    methods = {f.name: f for f in tree.body[0].body
               if isinstance(f, ast.FunctionDef)
               and f.name.startswith(STEP_PATH)}
    for name in ("_stage_step", "_stage_window", "_stage_pp_window",
                 "_stage_spec", "_stage_operands", "_dispatch_staged",
                 "_launch_step", "_run_decode", "_run_decode_pp",
                 "_run_spec_decode", "_prime_pipeline", "_pipeline_step"):
        assert name in methods, name
    puts = {name: _puts(fn) for name, fn in methods.items() if _puts(fn)}
    assert puts == {"_stage_operands": [("jax", "device_put")]}
