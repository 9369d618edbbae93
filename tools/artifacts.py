"""Evidence-artifact writing policy for bench/profile tools.

VERDICT r5 weak #7: an artifact was captured under one name and renamed
after the fact (`PARITY_TPU_r05.json` -> `PARITY_TPU_r05_initial.json`),
so following the evidence trail required timestamp forensics. Policy,
enforced by routing every evidence write through this module:

- artifacts are written under their FINAL name, directly — never via a
  temp file + rename, never renamed afterwards;
- multi-run artifacts are append-only JSONL (one JSON record per line,
  like LEDGER_r10.jsonl and real_ckpt_e2e's log): re-runs add
  records, they never rewrite history;
- single-record artifacts refuse to silently clobber an existing capture
  (pass overwrite=True only when regenerating the same evidence is the
  point, e.g. a re-run of the same bench round).

Crash-recovery SCRATCH state (bench.py's .bench_state.json) is exempt:
it is consumed by the supervisor within the run and is not evidence, so
its atomic tmp+replace is the right tool there.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict

_TMP_SUFFIXES = (".tmp", ".part", ".partial", "~")


def _check_final_name(path: str) -> None:
    base = os.path.basename(path)
    if base.endswith(_TMP_SUFFIXES) or base.startswith("."):
        raise ValueError(
            f"evidence artifact {path!r} must be written under its final "
            "name (no temp/hidden names — the whole point is that the "
            "name in the log is the name in the repo)")


def append_jsonl(path: str, record: Dict[str, Any]) -> None:
    """Append one JSON record to an append-only evidence log."""
    _check_final_name(path)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")


def write_json(path: str, record: Any, overwrite: bool = False) -> None:
    """Write a single-record artifact directly under its final name."""
    _check_final_name(path)
    if not overwrite and os.path.exists(path):
        raise FileExistsError(
            f"evidence artifact {path!r} already exists; artifacts are "
            "written once under their final name — pick a new name for a "
            "new capture, or pass overwrite=True to deliberately "
            "regenerate this one")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
