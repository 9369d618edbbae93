"""PR 39's two per-layer metrics over the pipeline's counters, on a
fixture of two scrapes. By hand, on the CPU:
`python -m pytest benchmark/tests/test_pipeline_metrics.py -q`."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
from harness import readers  # noqa: E402

METRICS = ("pipeline.discarded_step_share", "pipeline.reconciled_step_share")
with open(os.path.join(HERE, "tests", "fixtures",
                       "pipeline_counters.json")) as f:
    FIXTURE = json.load(f)


@pytest.mark.parametrize("program", ["with", "dropping", "without"])
@pytest.mark.parametrize("name", METRICS)
def test_the_shares_on_two_scrapes(name, program):
    """With the counters the share of the window's device steps; on a
    program without them (the parent) nothing, and the line leaves the
    metric out."""
    spec = readers.load_metric(name, HERE)
    case = FIXTURE[program]
    got = readers.evaluate(spec["expr"], {
        "prom": tuple(FIXTURE["prom"]), "engine": tuple(case["engine"])})
    want = case[name]
    assert got is None if want is None else got == pytest.approx(want)


def test_the_two_entries_are_every_cells():
    """(That an entry agrees with its file is test_harness's
    test_benchmark_json_names_units_and_files, for every metric.)"""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    found = [m for m in bench["per_layer"] if m["name"] in METRICS]
    assert tuple(m["name"] for m in found) == METRICS
    for entry, better in zip(found, ("lower", "higher")):
        assert entry == {
            "name": entry["name"], "unit": "%", "better": better,
            "source": "program_counter", "layer": "engine host loop",
            "moves": "output_tok_s"}   # no `workloads` key: every cell's
