"""PR 46's per-layer metric over the hand-over counters, on a fixture of
two scrapes. By hand, on the CPU:
`python -m pytest benchmark/tests/test_handover_metric.py -q`."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
from harness import readers  # noqa: E402

METRIC = "pipeline.handover_chained_share"
with open(os.path.join(HERE, "tests", "fixtures",
                       "handover_counters.json")) as f:
    FIXTURE = json.load(f)


@pytest.mark.parametrize("program",
                         ["with", "lone", "no_handover", "without"])
def test_the_share_on_two_scrapes(program):
    """With the counters the share of the window's changes of step kind
    that were made ahead, 0 where none was or the window held no change;
    on a program without them (the parent) nothing, and the line leaves
    the metric out."""
    spec = readers.load_metric(METRIC, HERE)
    case = FIXTURE[program]
    got = readers.evaluate(spec["expr"], {"engine": tuple(case["engine"])})
    want = case[METRIC]
    assert got is None if want is None else got == pytest.approx(want)


def test_the_entry_is_every_cells():
    """(That an entry agrees with its file is test_harness's
    test_benchmark_json_names_units_and_files, for every metric.)"""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(m for m in bench["per_layer"] if m["name"] == METRIC)
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine host loop",
        "moves": "output_tok_s"}   # no `workloads` key: every cell's


def test_the_counters_are_engine_metrics_fields():
    """The `engine` reader scrapes `EngineMetrics`: the two counters the
    hand-overs bring are fields of it."""
    from dynamo_tpu.engine.scheduler import EngineMetrics
    fields = EngineMetrics.__dataclass_fields__
    assert {"handovers", "handovers_chained"} <= set(fields)
