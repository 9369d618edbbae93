"""chip_smoke.py on a machine with no chip: without the rehearsal switch it
must fail and print no result; with it, it runs the same command on the
CPU and labels every line so that nothing reads as a pass on the chip."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


def test_no_chip_and_no_switch_is_a_failure_with_no_result():
    out = subprocess.run([sys.executable, SMOKE], env=ENV, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """The driver also runs the script with nothing else of the repo."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    out = subprocess.run([sys.executable, str(alone), "--rehearsal"],
                         env=ENV, cwd=tmp_path, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def _rehearse(*flags):
    out = subprocess.run([sys.executable, SMOKE, "--rehearsal", *flags],
                         env=ENV, cwd=REPO, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    raw = out.stdout.strip().splitlines()
    for line in raw:                       # every line labels itself
        assert "rehearsal" in line and "cpu" in line, line
    lines = [json.loads(ln) for ln in raw]
    last = lines[-1]
    assert last["ok"] is False             # never a pass on the chip
    assert last["rehearsal"] is True and last["rehearsal_passed"] is True
    assert last["device"]["platform"] == "cpu"
    return {ln["phase"]: ln for ln in lines if "phase" in ln}


def test_rehearsal_passes_and_labels_itself():
    phases = _rehearse("--phases", "aggregated,kernel")
    agg = phases["aggregated"]
    assert agg["status"] == "passed"
    assert agg["platform"] == "cpu" and agg["device"]["platform"] == "cpu"
    # the second identical round dispatched no program the first did not
    assert agg["programs_first_dispatched"][0] > 0
    assert agg["programs_first_dispatched"][1] == 0
    # a chip-only phase is never printed as passed
    assert phases["kernel"]["status"] == "skipped"


@pytest.mark.slow
def test_rehearsal_of_the_disagg_graph():
    """The SDK graph leg (tier 1 starts the same graph through
    tests/test_example_disagg.py; this adds the smoke's own checks)."""
    dis = _rehearse("--phases", "disagg,tp4")
    assert dis["disagg"]["status"] == "passed"
    assert dis["disagg"]["remote_prefills"] >= 1
    assert {w["platform"] for w in
            dis["disagg"]["workers"].values()} == {"cpu"}
    assert dis["tp4"]["status"] == "skipped"
