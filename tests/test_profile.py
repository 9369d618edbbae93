"""The program reads its own profiler capture (ISSUE 52).

- every equation of every served program of the tiny configurations lies
  in a scope of the closed list `observability/metrics.SCOPES`, and inside
  a layer body in at least one; every `named_scope` literal of the package
  is on the list;
- the reducer `observability/profile.py` on a fixture cut from a real v5e
  capture (`tests/fixtures/profile_v5e_mistral.json`: two mixed steps and
  one decode window of `mistral-7b.decode-closed`, as the plain planes the
  core takes, with the HLO `op_name`s of the ops it holds): self time
  under nesting, leaf and family, a fusion's scope, the dispatch <->
  program join by bucket, gap parts, and `busy_s` equal to the benchmark's
  own reducer on the same planes;
- the same pieces on made-up planes, where each rule can be read off.
"""
import collections
import functools
import gzip
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.observability import profile
from dynamo_tpu.observability.metrics import SCOPES, scope_family

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))
from harness import trace_reduce  # noqa: E402

# -- (a) the closed list ---------------------------------------------------------

# a scope of ours: lowercase words joined by dots, a slash between two
# (jax's own are an einsum's spec, `jit(...)`, a transform)
OURS = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)*(/[a-z0-9_.]+)?$")
TINY = {"dense": "rehearsal-tiny", "moe": "rehearsal-tiny-olmoe",
        "capacity-moe": "rehearsal-tiny-moe",
        "latent": "rehearsal-tiny-moonlight",
        "window": "rehearsal-tiny-mellum", "linear": "rehearsal-tiny-ling",
        "lead+window": "rehearsal-tiny-trinity",
        "state-space": "rehearsal-tiny-falcon-h1",
        "conv": "rehearsal-tiny-lfm2",
        "retention": "rehearsal-tiny-brumby"}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _walk(jaxpr, outer=(), out=None):
    """[(equation, the scopes around it, outermost first)] of every
    equation that holds no jaxpr of its own: an inner equation's name
    stack is relative to the equation that holds it."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        stack = outer + tuple(
            e.name for e in eqn.source_info.name_stack.stack
            if type(e).__name__ == "Scope")
        inner = list(_sub_jaxprs(eqn))
        for sub in inner:
            _walk(sub, stack, out)
        if not inner:
            out.append((eqn, stack))
    return out


@functools.lru_cache(maxsize=None)
def served_jaxprs(name, rows, chunk=16, pages=8, base_pages=8):
    """{"step", "window"}: the jaxpr of the engine's two programs for
    `benchmark/configs/<name>` AS SERVED: through `_packed`, the operands
    in one buffer (tests/test_trinity.program_texts traces the raw
    functions; the scope `step` and the unpacking are the wrapper's)."""
    from dynamo_tpu.engine import engine as eng
    from dynamo_tpu.engine.config import EngineConfig, with_kv_rows
    from dynamo_tpu.engine.scheduler import (
        window_ladder, window_table_pages,
    )
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf
    with open(os.path.join(REPO, "benchmark", "configs", name,
                           "config.json")) as f:
        cfg = with_kv_rows(config_from_hf(json.load(f), name=name))
    ecfg = EngineConfig()
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    wtable = functools.partial(window_table_pages, ecfg, cfg.sliding_window)
    window_pages = (rows + ecfg.max_prefill_batch) \
        * wtable(ecfg.max_prefill_chunk) if cfg.window_pool else 0
    cache = jax.eval_shape(lambda: llama.init_cache(
        cfg, 64, ecfg.page_size, window_pages))
    if cfg.state_leaves():
        cache = {**cache, **jax.eval_shape(lambda: llama.init_state(
            cfg, rows + ecfg.max_prefill_batch))}
    grid = (rows, chunk)
    shapes = {"tokens": grid, "positions": grid, "write_idx": grid,
              "wwrite_idx": grid, "page_table": (rows, pages),
              "base_table": (rows, base_pages), "stop_ids": (rows, 0)}
    dtypes = {"temperature": np.float32, "top_p": np.float32,
              "ignore_eos": np.bool_}
    state = ("state_slots",) * bool(cfg.state_leaves())
    nw = window_ladder(ecfg.decode_steps)[0]
    out = {}
    for key, fn, names, kw, first, wt in (
            ("step", functools.partial(
                eng._engine_step, cfg, (), None, None, False, False, False,
                None),
             eng.STEP_OPERANDS + state
             + ("wtable", "woff", "wwrite_idx") * bool(cfg.window_pool),
             dict(fed=True), (rows,), chunk),
            ("window", functools.partial(
                eng._engine_decode_window, cfg, (), None, nw,
                ecfg.page_size, False, False, False),
             eng.WINDOW_OPERANDS + state
             + ("wtable", "woff") * bool(cfg.window_pool),
             dict(carried=True), (rows, 3), 1)):
        if cfg.window_pool:
            shapes["wtable"] = (rows, wtable(wt))
        layout, buf = eng.pack_operands([
            np.zeros(shapes.get(n, (rows,)), dtypes.get(n, np.int32))
            for n in names])
        program = eng._packed(fn, names, **kw)
        out[key] = jax.make_jaxpr(
            lambda p, c, a, b: program(p, c, a, layout, b))(
            params, cache, jax.ShapeDtypeStruct(first, jnp.int32),
            jax.ShapeDtypeStruct(buf.shape, jnp.int32))
    return out


@pytest.mark.parametrize("program", ["step", "window"])
@pytest.mark.parametrize("kind", sorted(TINY))
def test_every_equation_lies_in_a_scope_of_the_list(kind, program):
    """No scope outside the list; every equation in at least one (the
    wrapper's `step` encloses the program, `layers.body` a layer's body);
    inside a layer body, the body's scope is on the stack."""
    name = TINY[kind]
    # 16 rows: where a recurrent-state model splits a step's rows
    rows = 16 if kind in ("linear", "state-space", "retention") else 8
    eqns = _walk(served_jaxprs(name, rows)[program].jaxpr)
    assert len(eqns) > 300
    strangers = collections.Counter(
        s for _, stack in eqns for s in stack
        if OURS.match(s) and s not in SCOPES)
    assert not strangers, f"scopes outside SCOPES: {dict(strangers)}"
    bare = [str(eqn.primitive) for eqn, stack in eqns
            if not any(s in SCOPES for s in stack)]
    assert not bare, f"{len(bare)} equations in no scope: {bare[:8]}"
    # a layer's matmuls lie in its body, under a narrower name than the
    # body's own
    dots = [stack for eqn, stack in eqns
            if eqn.primitive.name == "dot_general"
            and "layers.body" in stack]
    assert dots
    glue = [stack for stack in dots
            if profile.scope_of("/".join(stack)) in ("layers.body", "step")]
    assert not glue, f"matmuls of a layer in no narrower scope: {glue[:3]}"


def test_every_named_scope_of_the_package_is_on_the_list():
    """A new `jax.named_scope("...")` literal brings its name to SCOPES,
    and SCOPES lists nothing the package does not open (`_WO_SCOPE`: the
    output projection's scope by the layer's kind, models/llama.py)."""
    found = set()
    for root, _, files in os.walk(os.path.join(REPO, "dynamo_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name)) as f:
                text = f.read()
            found |= set(re.findall(r'named_scope[,(]\s*"([^"]+)"', text))
            for line in re.findall(
                    r"_WO_SCOPE(?: = \{[^}]*|\.get\([^\n]*)", text):
                found |= set(re.findall(r'"([a-z]+\.[a-z_.]+)"', line))
    assert found - set(SCOPES) == set()
    assert set(SCOPES) - found == set()


@pytest.mark.parametrize("path,leaf,family", [
    ("jit(engine_step)/step/layers.body/attention/attention.qkv/"
     "btd,de->bte/dot_general", "attention.qkv", "attention"),
    ("jit(engine_step)/step/layers.lead/layers.body/attention.window/"
     "attention/reduce_max", "attention.window", "attention"),
    ("jit(engine_step)/step/layers.body/attention.window/attention/"
     "attention.gather/gather", "attention.gather", "attention"),
    ("jit(engine_step)/step/layers.body/mlp.dense_lead/mlp/dot_general",
     "mlp.dense_lead", "mlp"),
    ("jit(f)/step/layers.body/block.parallel/ssm.conv/while/body/add",
     "block.parallel/ssm.conv", "ssm"),
    ("jit(f)/step/layers.body/block.parallel/attention/attention/exp",
     "block.parallel/attention", "attention"),
    ("jit(f)/step/layers.body/moe/moe.route/moe.route.groups/top_k",
     "moe.route.groups", "moe"),
    ("jit(f)/step/layers.body/attention/step.compact/gather",
     "step.compact", "step"),
    ("jit(f)/step/layers.body/add", "layers.body", "layers"),
    ("jit(f)/step/while/body/closed_call/mul", "step", "step"),
    ("jit(f)/jit(_where)/select_n", "", ""),
])
def test_an_op_name_counts_to_its_innermost_leaf(path, leaf, family):
    assert profile.scope_of(path) == leaf
    assert scope_family(leaf) == family


# -- (b) made-up planes: each rule by itself ------------------------------------------

def _us(x):
    return int(x * 1000)


HLO = '''HloModule jit_engine_step, is_scheduled=true

%fused_computation.1 (p: bf16[128,4096]) -> bf16[128,4096] {
  %p = bf16[128,4096]{1,0} parameter(0)
  ROOT %mul.9 = bf16[128,4096]{1,0} multiply(%p, %p), metadata={op_name="jit(engine_step)/step/layers.body/norm.attn/mul"}
}

%body.2 (arg: (s32[], bf16[128,4096])) -> (s32[], bf16[128,4096]) {
  %arg = (s32[], bf16[128,4096]{1,0}) parameter(0)
  %fusion.7 = bf16[128,4096]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(engine_step)/step/layers.body/attention/attention.qkv/dot_general"}
  %copy.3 = bf16[128,4096]{1,0} copy(%fusion.7), metadata={op_name="jit(engine_step)/step/layers.body/attention/attention.gather/gather"}
  %copy.4 = bf16[2,1024,16,128]{3,2,1,0} copy(%gte.2)
  ROOT %tuple.5 = (s32[], bf16[128,4096]{1,0}) tuple(%gte.0, %copy.3)
}

ENTRY %main.1 (a: bf16[128,4096]) -> bf16[128,4096] {
  %a = bf16[128,4096]{1,0} parameter(0)
  %while.1 = (s32[], bf16[128,4096]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.2, metadata={op_name="jit(engine_step)/step/while"}
  ROOT %fusion.9 = f32[32,32000]{1,0} fusion(%gte.9), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(engine_step)/step/head/dot_general"}
}
'''


def _op(name):
    """The event name a v5e trace gives an instruction of HLO above: its
    line up to the metadata."""
    line = next(ln for ln in HLO.splitlines()
                if re.match(rf"\s*(ROOT )?%{re.escape(name)} =", ln))
    return line.strip().removeprefix("ROOT ").split(", metadata=")[0]


def made_up_planes(skew_us=0.0):
    """Three runs of one step program [32,16] and one window between the
    second and the third, 1000 us each with 100 us between them; a run is
    a `while` of 800 us that holds two passes of fusion.7 (250 us), copy.3
    (100 us) and copy.4 (30 us), then fusion.9 (200 us). The first run was
    launched before the capture began. The host: dispatch (its run
    enqueued inside it), wait to 40 us behind the run's end, commit,
    worker.emit, worker.apply_pending, plan, upload."""
    ops, runs, host = [], [], []
    for k in range(4):
        t = k * 1100.0 - skew_us
        window = k == 2
        name = "jit_engine_decode_window_full(77)" if window \
            else "jit_engine_step(55)"
        runs.append((_us(t), _us(t + 1000), name, {"run_id": 10 + k}))
        if not window:
            ops.append((_us(t), _us(t + 800), _op("while.1"), {}))
            for j in range(2):
                u = t + 10 + j * 390
                ops += [(_us(u), _us(u + 250), _op("fusion.7"), {}),
                        (_us(u + 250), _us(u + 350), _op("copy.3"), {}),
                        (_us(u + 350), _us(u + 380), _op("copy.4"), {})]
            ops.append((_us(t + 800), _us(t + 1000), _op("fusion.9"), {}))
        else:
            ops.append((_us(t), _us(t + 1000), "%fusion.1 = f32[8]{0} "
                        "fusion(f32[8]{0} %p), kind=kLoop", {}))
        g = k * 1100.0 + 1000        # the run's end on the HOST's clock
        if k:
            stats = {"kind": "window", "rows": 32, "rung": 8, "seq": 40 + k,
                     "ahead": 0} if window else {
                "kind": "mixed", "rows": 32, "chunk": 16, "seq": 40 + k,
                "ahead": 0}
            host += [(_us(g - 1040), _us(g - 1005), "engine.dispatch",
                      stats),
                     (_us(g - 1012), _us(g - 1008), "DoEnqueueProgram",
                      {"run_id": 10 + k})]
        host += [(_us(g - 1000), _us(g + 40), "engine.wait", {}),
                 (_us(g + 41), _us(g + 42), "CompleteCallbacks",
                  {"run_id": 10 + k}),
                 (_us(g + 40), _us(g + 48), "engine.commit", {}),
                 (_us(g + 50), _us(g + 53), "worker.emit", {}),
                 (_us(g + 53.5), _us(g + 54), "worker.apply_pending", {}),
                 (_us(g + 56), _us(g + 58), "engine.plan", {}),
                 (_us(g + 58), _us(g + 60), "engine.upload", {})]
    return [("/device:TPU:0", [("XLA Modules", runs), ("XLA Ops", ops)]),
            ("/host:CPU", [("engine", sorted(host))])]


@pytest.fixture(scope="module")
def made_up():
    return profile.reduce_capture(made_up_planes(), {"step.hlo.txt": HLO})


def test_self_time_is_the_duration_less_what_is_nested():
    ops = [(0, 800, "while"), (10, 260, "a"), (260, 360, "b"),
           (400, 650, "a"), (820, 970, "c"), (20, 30, "in a")]
    assert profile.self_times(ops) == [800 - 250 - 100 - 250, 240, 100,
                                       250, 150, 10]


def test_a_container_is_its_overhead_and_a_fusion_its_namers_scope(made_up):
    step = next(p for p in made_up["programs"] if p["kind"] == "mixed")
    assert (step["program"], step["bucket"], step["runs"]) == (
        "jit_engine_step", "32x16", 3)
    leaf = {k: v["ms"] for k, v in step["scopes"]["leaf"].items()}
    # the while's own 800 - 2 * 380 us, not its body's
    assert leaf["containers"] == pytest.approx(0.040)
    # fusion.7's body multiplies under norm.attn; the fusion's OWN line
    # names attention.qkv, and that is where it counts
    assert leaf["attention.qkv"] == pytest.approx(0.500)
    assert "norm.attn" not in leaf
    assert leaf["attention.gather"] == pytest.approx(0.200)
    assert leaf["head"] == pytest.approx(0.200)
    # copy.4 carries no op_name: the compiler's own
    assert leaf["unscoped"] == pytest.approx(0.060)
    assert leaf["idle_in_program"] == pytest.approx(0.0, abs=1e-9)
    assert sum(leaf.values()) == pytest.approx(step["device_ms_mean"])
    family = {k: v["ms"] for k, v in step["scopes"]["family"].items()}
    assert family["attention"] == pytest.approx(0.700)
    assert sum(v["share"] for v in step["scopes"]["leaf"].values()) \
        == pytest.approx(1.0)
    # what PRs 26, 47, 48 and 51 hunted by hand
    assert step["copies"] == {"attention.gather": pytest.approx(0.200),
                              "unscoped": pytest.approx(0.060)}
    top = made_up["top_ops"][0]
    assert (top["op"], top["opcode"], top["scope"], top["bucket"]) == (
        "fusion.7", "fusion", "attention.qkv", "32x16")
    assert top["out_bytes"] == 128 * 4096 * 2 and top["events"] == 6


def test_a_run_takes_the_bucket_of_its_dispatch(made_up):
    """Through the run's id; the run launched before the capture began
    takes what its executable's other runs were launched as."""
    assert made_up["dispatches"] == {"seen": 3, "joined": 3,
                                     "engine_runs": 4}
    rows = {(p["program"], p["kind"], p["bucket"]): p["runs"]
            for p in made_up["programs"]}
    assert rows == {("jit_engine_step", "mixed", "32x16"): 3,
                    ("jit_engine_decode_window_full", "window", "32xw8"): 1}
    window = next(p for p in made_up["programs"] if p["kind"] == "window")
    assert window["scoped_from_hlo"] is False
    assert list(window["scopes"]["leaf"]) == ["unscoped", "idle_in_program"]


def test_the_join_by_order_where_a_trace_has_no_run_ids():
    planes = made_up_planes()
    for _, lines in planes:
        for _, evs in lines:
            for ev in evs:
                ev[3].pop("run_id", None)
    red = profile.reduce_capture(planes, {"step.hlo.txt": HLO})
    # by order the first dispatch (a mixed step's) pairs with the first
    # run it can: one executable then keeps one bucket only if the run
    # before the capture is skipped
    rows = {(p["kind"], p["bucket"]): p["runs"] for p in red["programs"]}
    assert rows == {("mixed", "32x16"): 3, ("window", "32xw8"): 1}
    assert red["device"]["clock_shift_ns"] == 0.0


def test_a_dispatch_made_ahead_joins_the_run_after_the_one_in_flight():
    """Two deep: dispatch k + 1 opens while run k is on the device."""
    runs = [(_us(k * 1000.0), _us(k * 1000.0 + 990), "jit_engine_step(5)",
             {"run_id": k}) for k in range(4)]
    host = []
    for k in range(1, 4):
        # launched a whole run ahead, under the run before it
        host += [(_us((k - 1) * 1000.0 + 100), _us((k - 1) * 1000.0 + 130),
                  "engine.dispatch", {"kind": "mixed", "rows": 8,
                                      "chunk": 64 if k == 2 else 16,
                                      "seq": k, "ahead": 1}),
                 (_us((k - 1) * 1000.0 + 120), _us((k - 1) * 1000.0 + 125),
                  "DoEnqueueProgram", {"run_id": k})]
    red = profile.reduce_capture(
        [("/device:TPU:0", [("XLA Modules", runs)]),
         ("/host:CPU", [("t", sorted(host))])])
    assert red["dispatches"]["joined"] == 3
    # run 2 alone was launched as [8,64]; runs 0 (before the capture), 1
    # and 3 share the first bucket their executable was launched with
    got = sorted((p["bucket"], p["runs"]) for p in red["programs"])
    assert got == [("8x16", 3), ("8x64", 1)]


def test_a_gap_goes_to_the_part_of_the_host_loop_that_covers_it(made_up):
    gaps = made_up["idle_gaps"]
    assert gaps["count"] == 3 and gaps["seconds"] == pytest.approx(300e-6)
    # 100 us behind a run: 40 wait's tail, 8 commit, 2 + 3 + 0.5 + 0.5 + 2
    # resume / emit / ... / submit, 2 plan, 2 upload, 35 dispatch
    assert set(gaps["by_part"]) == {"wait"}
    assert gaps["by_kinds"] == {
        "mixed->mixed": {"count": 1, "seconds": pytest.approx(100e-6)},
        "mixed->window": {"count": 1, "seconds": pytest.approx(100e-6)},
        "window->mixed": {"count": 1, "seconds": pytest.approx(100e-6)}}
    loop = profile.host_loop([
        (0, 40, "engine.wait"), (40, 48, "engine.commit"),
        (50, 53, "worker.emit"), (54, 55, "worker.apply_pending"),
        (57, 58, "engine.plan"), (10, 20, "PjitFunction(x)")])
    assert [part for _, _, part in loop] == [
        "wait", "commit", "resume", "emit", "emit", "apply_pending",
        "submit", "plan"]
    assert loop[2][:2] == (48, 50) and loop[6][:2] == (55, 57)


def test_the_devices_clock_is_moved_onto_the_hosts():
    """A device plane stamped 1500 us early: no run may start before it
    was enqueued, so the events move later by what that takes, and the
    gaps fall where they fell."""
    plain = profile.reduce_capture(made_up_planes())
    early = profile.reduce_capture(made_up_planes(skew_us=1500.0))
    assert plain["device"]["clock_shift_ns"] == 0.0
    lo, hi = early["device"]["clock_shift_bounds_ns"]
    assert lo <= 1_500_000 <= hi and hi - lo < 60_000
    assert early["device"]["clock_shift_ns"] == lo
    assert set(early["idle_gaps"]["by_part"]) == {"wait"}
    assert early["idle_gaps"]["by_kinds"] == plain["idle_gaps"]["by_kinds"]


def test_both_reducers_agree_on_the_devices_seconds(made_up):
    planes = made_up_planes()
    theirs = trace_reduce.reduce_planes(
        [(p, [(ln, [ev[:3] for ev in evs]) for ln, evs in lines])
         for p, lines in planes])
    for key in ("window_s", "busy_s", "chips"):
        assert made_up["device"][key] == pytest.approx(theirs[key])
    assert made_up["device"]["idle_s"] == pytest.approx(
        theirs["window_s"] - theirs["busy_s"])
    assert made_up["idle_gaps"]["count"] == theirs["idle_gap_count"]
    assert profile.MIN_GAP_NS == trace_reduce.MIN_GAP_S * 1e9


def test_the_top_level_is_what_the_route_answers_with(made_up):
    top = profile.top_level(made_up)
    assert set(top) == {"device", "programs", "idle_gaps"}
    assert all("scopes" not in p for p in top["programs"])
    assert len(json.dumps(top)) < 2000


def test_the_engine_imports_nothing_of_the_reducer():
    """The step path pays nothing for it: no module of the engine, the
    models or the ops imports `observability.profile`; the worker starts
    it as a child."""
    for sub in ("engine", "models", "ops"):
        for root, _, files in os.walk(os.path.join(REPO, "dynamo_tpu", sub)):
            for name in files:
                if name.endswith(".py"):
                    with open(os.path.join(root, name)) as f:
                        assert "observability.profile" not in f.read(), name
    with open(os.path.join(REPO, "dynamo_tpu", "llm", "worker.py")) as f:
        text = f.read()
    assert "import profile" not in text and \
        '"-m", "dynamo_tpu.observability.profile"' in text
    # and the core reads planes and texts alone
    with open(profile.__file__) as f:
        own = f.read()
    assert not re.search(r"^\s*(from|import) dynamo_tpu\.(engine|llm|models"
                         r"|ops)", own, re.M)


def test_the_hlo_parser_lives_in_one_place():
    """tools/pool_ops.py reads optimised HLO lines with the reducer's
    parser, not with a second one."""
    with open(os.path.join(REPO, "tools", "pool_ops.py")) as f:
        text = f.read()
    assert "from dynamo_tpu.observability.profile import" in text
    for name in ("_INSTR", "_OPNAME", "_SHAPE", "ITEMSIZE"):
        assert not re.search(rf"^{name} = ", text, re.M), name
    module, names = profile.instruction_scopes(HLO)
    assert module == "jit_engine_step"
    assert names["fusion.7"].endswith("attention.qkv/dot_general")
    assert names["copy.4"] == "" and "while.1" in names
    assert profile.shape_bytes("(s32[], bf16[128,4096]{1,0})") == 1 << 20


# -- (c) a real v5e capture ---------------------------------------------------------

FIXTURE = os.path.join(REPO, "tests", "fixtures", "profile_v5e_mistral.json.gz")


@pytest.fixture(scope="module")
def v5e():
    with gzip.open(FIXTURE, "rt") as f:
        held = json.load(f)
    planes = [(p, [(ln, [tuple(ev) for ev in evs]) for ln, evs in lines])
              for p, lines in held["planes"]]
    return held, planes, profile.reduce_capture(planes, held["programs"])


def test_the_fixture_is_a_v5e_capture_of_the_serving_loop(v5e):
    held, planes, red = v5e
    assert held["device_kind"] == "TPU v5 lite"
    assert os.path.getsize(FIXTURE) < 400_000
    kinds = {(p["kind"], p["bucket"]) for p in red["programs"]}
    assert ("mixed", "32x16") in kinds
    assert any(k == "window" for k, _ in kinds)
    assert red["dispatches"]["joined"] >= 3


def test_a_real_steps_scopes_sum_to_its_device_time(v5e):
    """Self times under real nesting (a layer scan's `while`, the compact
    step's `conditional`s) tile a run: with `unscoped`, `containers` and
    the idle inside it they sum to the program's device time."""
    _, _, red = v5e
    for p in red["programs"]:
        leaf = p["scopes"]["leaf"]
        assert sum(v["share"] for v in leaf.values()) \
            == pytest.approx(1.0, abs=1e-6)
        assert sum(v["ms"] for v in leaf.values()) \
            == pytest.approx(p["device_ms_mean"], rel=1e-6)
    step = next(p for p in red["programs"] if p["bucket"] == "32x16")
    assert step["scoped_from_hlo"]
    leaf = step["scopes"]["leaf"]
    # the containers are their overhead: a [32,16] step of 16 layers is
    # not 8 s of `while` in a 4 s slice
    assert leaf["containers"]["share"] < 0.05
    assert leaf.get("unscoped", {"share": 0.0})["share"] < 0.15
    family = step["scopes"]["family"]
    for name in ("attention", "mlp", "kv", "norm", "head", "sampler"):
        assert family[name]["ms"] > 0, name
    assert family["attention"]["ms"] + family["mlp"]["ms"] \
        > 0.5 * step["device_ms_mean"]


def test_a_real_fusion_counts_to_the_scope_that_names_it(v5e):
    held, _, red = v5e
    fusions = [op for op in red["top_ops"] if op["opcode"] == "fusion"
               and op["scope"] != "unscoped"]
    assert fusions
    module, names = profile.instruction_scopes(
        next(iter(held["programs"].values())))
    for op in fusions[:5]:
        texts = [profile.instruction_scopes(t)[1]
                 for t in held["programs"].values()]
        assert any(profile.scope_of(t.get(op["op"], "")) == op["scope"]
                   for t in texts), op


def test_both_reducers_agree_on_the_real_capture(v5e):
    _, planes, red = v5e
    theirs = trace_reduce.reduce_planes(
        [(p, [(ln, [ev[:3] for ev in evs]) for ln, evs in lines])
         for p, lines in planes])
    assert red["device"]["busy_s"] == pytest.approx(theirs["busy_s"],
                                                    rel=5e-3)
    assert red["device"]["window_s"] == pytest.approx(theirs["window_s"])
    assert red["idle_gaps"]["count"] == pytest.approx(
        theirs["idle_gap_count"], abs=2)


def test_real_gaps_have_kinds_and_parts(v5e):
    _, _, red = v5e
    gaps = red["idle_gaps"]
    # the cut holds ONE gap of the slice's four: the device idle between a
    # drained window and the mixed step behind it
    assert gaps["count"] == 1 and list(gaps["by_kinds"]) == ["window->mixed"]
    assert 1e-3 < gaps["seconds"] < 20e-3
    parts = set(gaps["by_part"])
    assert parts <= {"plan", "upload", "dispatch", "compile", "wait",
                     "commit", "resume", "emit", "apply_pending", "submit",
                     "none"}
    assert any("->" in k or k.startswith("in ") for k in gaps["by_kinds"])
    lo, hi = red["device"]["clock_shift_bounds_ns"]
    assert lo is not None and hi is not None and lo <= hi
