"""The rule of BENCHMARK.json's `per_layer` since PR 49: one file holds an
expression; a cell is named in a list, never in a metric's name; an entry
that reads what every engine exports has no `workloads` key, so a later
configuration's cell gets it for nothing.

Held against a kept fixture, `fixtures/per_layer_pr48.json` (PR 48's
`per_layer`, each entry with its file's `expr`): every (cell, former name)
pair still has an entry that reads that expression. PR 54 folded the last
52 copies into their accepted metrics' lists, so no file of the tree
holds `"expr_of": "<accepted metric>"` today; the mechanism stays
(`harness/readers.load_metric`): a later PR that needs an accepted metric
in a cell whose list it may not edit declares its stand-in that way, by a
file it adds, and the next `benchmark` PR folds it. Every assertion here
is a rule, none a count of today's entries: a PR that adds a cell, an
entry or such a file leaves this file green.

By hand, on the CPU: `python -m pytest benchmark/tests/test_benchmark_lists.py -q`.
"""
import json
import os
import re
import types

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
from harness import readers  # noqa: E402

PREFIXED = re.compile(r"\.(mla|ling|swa|afm|fh1|conv)_")
# distinct expressions whose constants hold for one cut of one model: they
# stay under their names (ISSUE 49, item 5)
OWN = {"device.mla_window_roofline", "device.ling_window_roofline",
       "device.swa_window_roofline", "device.afm_window_roofline",
       "device.fh1_window_roofline", "device.fh1_ssm_kernel_share",
       "device.fh1_ssm_step_roofline"}
# what `trace_window_step_median_s` took the place of (ISSUE 49, item 6)
OLD_STEP = {"op": "div", "args": [
    {"trace_module_median_s": "engine_decode_window_full"},
    {"run": "decode_steps"}]}
NEW_STEP = {"trace_window_step_median_s": "engine_decode_window"}
BASE = "engine_decode_window"


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
FORMER = load("tests", "fixtures", "per_layer_pr48.json")
FILES = {f[:-5]: load("layer_metrics", f)
         for f in sorted(os.listdir(os.path.join(HERE, "layer_metrics")))}
STAND_INS = {n: spec["expr_of"] for n, spec in FILES.items()
             if "expr_of" in spec}


def with_the_leaf(expr):
    """A former expression with the window-step leaf where item 6 put it."""
    if expr == OLD_STEP:
        return dict(NEW_STEP)
    if "args" in expr:
        return {**expr, "args": [with_the_leaf(a) for a in expr["args"]]}
    return expr


def key(expr):
    return json.dumps(expr, sort_keys=True)


NOW = {m["name"]: {**m, "expr": readers.load_metric(m["name"], HERE)["expr"]}
       for m in BENCH["per_layer"]}


E2E_CELLS = {m["name"]: m.get("workloads") for m in BENCH["end_to_end"]}


def covers(entry, cell):
    """As run.py's `in_cell`: a listed cell's; without a list every cell's
    that reports the end-to-end metric the entry moves."""
    cells = entry.get("workloads", E2E_CELLS.get(entry["moves"]))
    return cells is None or cell in cells


# PR 54's second round: `itl_p95_ms` is end to end in the cells where its
# runs repeat, not in Ling's (PERF.md section 2). What named it and is read
# in every cell names the pace, which every cell reports: a stall or a
# compile is one long gap a stream, which a 95th percentile of ~175 000
# gaps does not see and every stream's mean gap does, and a class of gaps
# is a term of that mean. The tail's own sibling still names it and is not
# Ling's any more; the demoted tail stands there as `stream.itl_p95_ms`.
NOW_MOVES_THE_PACE = {
    "sched.decode_stall_share", "warmup.compiles_in_window",
    "warmup.jax_compiles_in_window", "stream.gap_mixed_share",
    "stream.gap_mixed_ms", "stream.gap_window_ms", "stream.gap_multi_share",
    "stream.gap_multi_ms", "stream.burst_share"}
NOT_END_TO_END = {"itl_p95_ms": {"ling-3.0-flash-vl.decode-closed"}}


def now_named(former, cell):
    """The entries of today that read `former`'s expression in `cell`."""
    want = key(with_the_leaf(former["expr"]))
    return [m for m in NOW.values()
            if key(m["expr"]) == want and covers(m, cell)]


def moves_today(former):
    """What a former entry's expression moves today: the pace where its
    entry of today is one of `NOW_MOVES_THE_PACE`, else what it moved."""
    want = key(with_the_leaf(former["expr"]))
    paced = any(key(NOW[n]["expr"]) == want for n in NOW_MOVES_THE_PACE)
    return "tpot_p50_ms" if paced else former["moves"]


def cells_of(former):
    """The cells a former entry was read in, but those where what it
    moves is not end to end any more."""
    gone = NOT_END_TO_END.get(moves_today(former), ())
    return [c for c in former.get("workloads", FORMER["cells"])
            if c not in gone]


PAIRS = [(m["name"], cell) for m in FORMER["per_layer"]
         for cell in cells_of(m)]


# -- (a) no measurement went with a name --------------------------------------

@pytest.mark.parametrize("name,cell", PAIRS)
def test_a_former_metric_is_still_read_in_its_cell(name, cell):
    """Every (former name, cell) pair of PR 48: ONE entry of today reads
    that expression there, with the former's unit, direction, layer and
    end-to-end metric."""
    former = next(m for m in FORMER["per_layer"] if m["name"] == name)
    found = now_named(former, cell)
    assert len(found) == 1, (name, cell, [m["name"] for m in found])
    for k in ("unit", "better", "layer", "moves", "source"):
        want = moves_today(former) if k == "moves" else former[k]
        assert found[0][k] == want, (name, found[0]["name"], k)


def test_what_is_not_end_to_end_in_a_cell_is_per_layer_there():
    """The one pair that (a) leaves out is the tail's sibling in the cell
    where the tail is not end to end; the tail itself is read there per
    layer, by the end-to-end arithmetic, and moves what the cell reports."""
    left_out = {(m["name"], c) for m in FORMER["per_layer"]
                for c in m.get("workloads", FORMER["cells"])} - set(PAIRS)
    assert left_out == {("stream.itl_p99_ms",
                         "ling-3.0-flash-vl.decode-closed")}
    for metric, cells in NOT_END_TO_END.items():
        # a list, so a later PR's cell is not on it either until a
        # `benchmark` PR appends it: rules, not a count of today's cells
        assert not set(E2E_CELLS[metric]) & cells
        assert set(E2E_CELLS[metric]) | cells >= set(FORMER["cells"])
        for cell in cells:
            mine = [m for m in NOW.values() if covers(m, cell)]
            assert not [m["name"] for m in mine if m["moves"] == metric]
            assert "stream." + metric in {m["name"] for m in mine}
    assert NOW["stream.itl_p95_ms"]["expr"] == {"op": "mul", "args": [
        {"const": 1000}, {"client": "itl_p95_s"}]}


# -- (b) the host loop is every cell's ----------------------------------------

HOST_LOOP = sorted({now["name"] for m in FORMER["per_layer"]
                    if m["layer"] == "engine host loop"
                    for c in m.get("workloads", FORMER["cells"])
                    for now in now_named(m, c)})


@pytest.mark.parametrize("cell", CELLS)
def test_every_host_loop_entry_is_the_cells(cell):
    """What PR 48 read of the host loop in ANY cell is read in every cell
    there is, those that later PRs add too."""
    assert HOST_LOOP
    assert [n for n in HOST_LOOP if not covers(NOW[n], cell)] == []


# -- (c) the rule ----------------------------------------------------------------

def test_one_file_holds_an_expression():
    """No two files under layer_metrics/ hold equal `expr`. The free room
    and what is left to fold are printed, not pinned."""
    held = {}
    for name, spec in FILES.items():
        if "expr" in spec:
            assert key(spec["expr"]) not in held, \
                (name, held[key(spec["expr"])])
            held[key(spec["expr"])] = name
    print(f"per_layer: {len(NOW)} of 128, {128 - len(NOW)} free; "
          f"{len(STAND_INS)} files stand in for an accepted metric and "
          f"wait for a benchmark PR to fold them into its list")
    assert len(NOW) <= 128


@pytest.mark.parametrize("name", sorted(STAND_INS))
def test_a_stand_in_is_the_accepted_metric_in_another_cell(name):
    """A file with `expr_of`: the named file holds the expression itself,
    both are listed with one unit, direction, layer, end-to-end metric and
    source, the named one first, and no cell reads both."""
    of = STAND_INS[name]
    assert "expr" not in FILES[name] and "expr" in FILES[of]
    listed = [m["name"] for m in BENCH["per_layer"]]
    assert listed.index(of) < listed.index(name)
    for k in ("unit", "better", "layer", "moves", "source"):
        assert NOW[name][k] == NOW[of][k], k
    for k in ("unit", "better", "layer", "moves", "reader"):
        assert FILES[name][k] == FILES[of][k], k
    assert "workloads" in NOW[name] and "workloads" in NOW[of]
    assert not set(NOW[name]["workloads"]) & set(NOW[of]["workloads"])


def test_expr_of_takes_one_step_and_stands_in_place_of_expr(tmp_path):
    os.makedirs(tmp_path / "layer_metrics")

    def put(name, **more):
        with open(tmp_path / "layer_metrics" / f"{name}.json", "w") as f:
            json.dump({"name": name, "reader": "engine", **more}, f)
    put("a", expr={"engine": "engine_steps"})
    put("b", expr_of="a")
    put("c", expr_of="b")
    put("d", expr_of="a", expr={"const": 1})
    put("e", expr_of="nothing")
    assert readers.load_metric("b", str(tmp_path))["expr"] \
        == {"engine": "engine_steps"}
    for name, error in (("c", ValueError), ("d", ValueError),
                        ("e", FileNotFoundError)):
        with pytest.raises(error):
            readers.load_metric(name, str(tmp_path))


def test_a_cell_is_named_in_a_list_not_in_a_name():
    """The six families' prefixes are on the expressions whose constants
    hold for one cut of one model, and on nothing else: a stand-in is
    named for the mechanism and its cell too (`moe.toy_experts_hit` in
    the throw-away copy below carries no family's prefix)."""
    prefixed = {n for n in NOW if PREFIXED.search(n)}
    assert prefixed <= OWN


def test_in_no_cell_do_two_entries_read_one_expression():
    for cell in CELLS:
        seen = {}
        for m in NOW.values():
            if covers(m, cell):
                assert key(m["expr"]) not in seen, \
                    (cell, m["name"], seen[key(m["expr"])])
                seen[key(m["expr"])] = m["name"]


def test_every_entry_has_a_file_and_every_file_an_entry():
    assert set(FILES) - set(NOW) == {"device.collective_share"}  # 4 chips
    assert set(NOW) <= set(FILES)
    for name in ("device.decode_step_ms", "device.decode_window_roofline"):
        assert name not in FILES   # a median over every rung / decode_steps


def test_a_list_of_every_cell_is_no_list():
    """An entry that names all the cells there are is read as every
    cell's today and shuts the next cell out tomorrow: it has no key."""
    full = {m["name"] for m in BENCH["per_layer"]
            if set(m.get("workloads", ())) >= set(CELLS)}
    assert full == set()


# -- (d) the window-step leaf ----------------------------------------------------

FULL = {"jit_" + BASE + "_full": [0.080, 0.088, 0.096],
        "jit_" + BASE + "_w2": [0.030, 0.032],
        "jit_" + BASE + "_w1": [0.020], "jit_engine_step": [0.5]}


@pytest.mark.parametrize("modules,steps,step_s", [
    (FULL, 8.0, 0.088 / 8),
    ({k: v for k, v in FULL.items() if "_full" not in k}, 2.0, 0.031 / 2),
    ({"jit_engine_step": [0.5]}, None, None)])
def test_the_leaf_reads_the_longest_rung_the_slice_holds(modules, steps,
                                                         step_s):
    ctx = {"trace": {"modules": modules}, "run": {"decode_steps": 8}}
    got = readers.evaluate(NEW_STEP, ctx)
    assert got == step_s
    assert readers.evaluate({"trace_window_rung_steps": BASE}, ctx) == steps
    # where the slice holds a full rung: the old expression, digit for digit
    old = readers.evaluate(OLD_STEP, ctx)
    assert old == got if "jit_" + BASE + "_full" in modules else old is None
    assert readers.evaluate(NOW["device.window_step_ms"]["expr"], ctx) == (
        None if got is None else 1000 * got)


def test_the_leaf_parses_the_programs_own_names():
    """What tier 1's `_leaves` cannot check yet (it knows four leaves):
    the names `NativeEngine._window_name` gives a ladder are the ones the
    leaf takes its step counts from."""
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import window_ladder
    sizes = window_ladder(8)
    eng = types.SimpleNamespace(_window_sizes=sizes)
    modules = {"jit_" + NativeEngine._window_name(eng, nw): [float(nw)]
               for nw in sizes}
    assert len(modules) == len(sizes) > 1
    assert readers.window_rung(modules, BASE, sizes[0]) == (sizes[0],
                                                            float(sizes[0]))
    for nw in sizes[1:]:
        one = {k: v for k, v in modules.items() if v == [float(nw)]}
        assert readers.window_rung(one, BASE, sizes[0]) == (nw, float(nw))
    # every listed expression that reads the ladder names it as the
    # engine does, whatever later PRs add: tier 1's rename guard
    # (tests/test_step_tracing.py `_leaves`) does not know these leaves
    uses = {n: re.findall(r'"trace_window_\w+": "([^"]*)"', key(m["expr"]))
            for n, m in NOW.items() if "trace_window_" in key(m["expr"])}
    assert {"device.window_step_ms", "device.window_rung_steps",
            "device.window_roofline"} | {n for n in OWN if "roofline" in n
                                         and "ssm" not in n} <= set(uses)
    assert {b for bases in uses.values() for b in bases} == {BASE}


# -- a PR that only adds leaves this file green ---------------------------------

def test_what_the_next_configuration_brings_breaks_no_rule_here(tmp_path):
    """A throw-away copy with what a `model_config` PR may bring and no
    edit: one more cell, an entry for a new expression, a host-loop entry of
    its own cell, and ONE stand-in for an accepted mechanism metric whose
    list it may not touch. Every other test of this file passes there."""
    import shutil
    import subprocess
    import sys
    root = tmp_path / "repo"
    shutil.copytree(HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dynamo_tpu"), root / "dynamo_tpu")
    b = json.loads(json.dumps(BENCH))
    cell = "toy-conv.decode-closed"
    b["workloads"].append({**b["workloads"][0], "name": cell})
    accepted = NOW["moe.experts_hit"]
    new = [("conv.toy_tail_share", "the model layer",
            {"expr": {"engine": "engine_steps"}}),
           ("host.toy_ms", "engine host loop", {"expr": {"const": 1}}),
           ("moe.toy_experts_hit", accepted["layer"],
            {"expr_of": "moe.experts_hit"})]
    for name, layer, body in new:
        entry = {**{k: v for k, v in accepted.items() if k != "expr"},
                 "name": name, "layer": layer, "workloads": [cell]}
        b["per_layer"].append(entry)
        spec = {**{k: FILES["moe.experts_hit"][k]
                   for k in ("unit", "better", "moves", "reader")},
                "name": name, "layer": layer, **body}
        with open(root / "benchmark" / "layer_metrics" / f"{name}.json",
                  "w") as f:
            json.dump(spec, f)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-k", "not next_configuration",
         str(root / "benchmark" / "tests" / "test_benchmark_lists.py")],
        cwd=root / "benchmark", capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stdout[-3000:]
    assert " passed" in run.stdout and "failed" not in run.stdout
