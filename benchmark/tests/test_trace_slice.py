"""A traced run's slice always holds a decode window (run.py
`slice_with_a_window`, PR 54): the slice is held its planned length where
a window was dispatched inside it, and otherwise on, in steps, until one
was and has had a step's time to run, within twice the planned length.

By hand, on the CPU: `python -m pytest benchmark/tests/test_trace_slice.py -q`.
"""
import asyncio
import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_run", os.path.join(HERE, "run.py"))
run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(run)


@pytest.mark.parametrize("counts,slept,got", [
    # a window inside the planned slice: held exactly as before PR 54
    ([5, 9], [4.0], (4, 4)),
    # none in the slice, one in the third step: that step and one more
    ([5, 5, 5, 5, 6, 8], [4.0, 0.5, 0.5, 0.5, 0.5], (0, 3)),
    # one in the very last step the budget has: 2 x the slice in all
    ([5] * 8 + [6, 6], [4.0] + [0.5] * 8, (0, 1)),
    # none at all: the trace is stopped inside twice the slice and the
    # window metrics read nothing, as they did
    ([5] * 9, [4.0] + [0.5] * 7, (0, 0))])
def test_the_slice_is_held_until_a_window_was_dispatched(counts, slept, got):
    polls, sleeps = iter(counts), []

    async def windows():
        return next(polls)

    async def sleep(seconds):
        sleeps.append(seconds)

    assert asyncio.run(run.slice_with_a_window(
        windows, 4.0, sleep=sleep)) == got
    assert sleeps == slept and sum(sleeps) <= 2 * 4.0
    assert next(polls, None) is None    # no scrape more than was needed
