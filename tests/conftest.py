"""Test configuration: force CPU with an 8-device virtual mesh.

Mirrors the reference's hardware-independent test strategy (SURVEY.md §4.5):
the reference tests its runtime with closure engines and a mock network; we
test our JAX engine and sharding on a virtual 8-device CPU mesh so no TPU is
required. JAX_PLATFORMS=cpu in the environment is all it takes; it is set
here, before jax is imported, so a bare `pytest` never touches a chip.
"""
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# the full suite on one CPU core can starve lease heartbeats past TTL/3,
# falsely expiring workers mid-test (observed flake: kv-events test);
# tests that exercise expiry override dist.LEASE_TTL_S directly
os.environ.setdefault("DYN_LEASE_TTL_S", "60")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# persistent XLA compilation cache (utils/launch.py: JAX_COMPILATION_CACHE_DIR
# if set, else <checkout>/.jax_cache): the suite builds dozens of engines
# whose tiny-model programs are HLO-identical (oracle/twin pairs, module
# fixtures across files); the disk cache dedupes them ACROSS engine
# instances and pytest runs — measured 25s -> 8s on test_mixed_steps
# alone, and it is the difference between the full suite fitting its
# 870s tier-1 budget and timing out. Keyed by HLO+config hash, so
# config/backend changes can never serve a stale program.
from dynamo_tpu.utils.launch import enable_compile_cache  # noqa: E402

enable_compile_cache()


import gc  # noqa: E402

import pytest  # noqa: E402

_gc_epoch = [0]


@pytest.fixture(autouse=True)
def _finalize_asyncio_cycles_between_tests():
    """Collect cyclic garbage after every test, BEFORE the next test
    opens sockets. A test that abandons asyncio objects mid-flight (e.g.
    after SIGKILLing a peer process, test_queue_push_survives_sigkill)
    leaves transport<->protocol<->task cycles for the cycle collector;
    if that collection happens during a LATER test's event loop, the
    stale transports' __del__ close raw fd NUMBERS that the new loop has
    since reused for its own sockets — observed as the next test's
    streams silently hanging to their 30s/60s timeouts. The collect runs
    at SETUP of the following test (pytest itself keeps the previous
    item's frames referenced until the next one begins, so teardown-time
    collection finds the cycles still live), closing those fds while the
    numbers are still unused.

    A FULL collect scans every tracked object, and the suite's heap only
    grows (jit program caches, module state): measured ~0.07s/test early
    in the run but ~1.4s/test by test 600 — 583s of an 1123s full-suite
    wall, tipping tier-1 past its 870s budget. gc.freeze() moves the
    stable baseline out of the per-test scan, so each collect only walks
    objects allocated since the last freeze (the previous few tests —
    exactly where abandoned transport cycles live, since freezes also
    happen at setup, before any of the current window's tests ran).
    Every 50 tests, unfreeze + full collect + refreeze at this same safe
    point reclaims anything that was live at an earlier freeze and has
    died since, so frozen-then-dead cycles (and their fds) are bounded
    to a 50-test window instead of leaking for the whole run."""
    if _gc_epoch[0] % 50 == 0:
        gc.unfreeze()
        gc.collect()
        gc.freeze()
    else:
        gc.collect()
    _gc_epoch[0] += 1
    yield
