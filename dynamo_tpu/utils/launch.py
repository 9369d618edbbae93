"""What every launcher does around building an engine: place the
persistent XLA compilation cache, and name the devices in its READY line.

Compile cache. A cold TPU server start compiles every bucketed program it
dispatches; the persistent cache turns the second start (and every child
process of one run) into cache reads. The directory is part of JAX's
cache key, so it must be a FIXED path — never a temp name, pid or
timestamp:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; no directory is
  set in code, so the operator (or the machine image) places the cache.
- unset: ``<checkout>/.jax_cache`` (git-ignored).

``enable_compile_cache`` is called by dynamo_tpu.run, sdk.run_service,
bench.py, chip_smoke.py's children, tools/tpu_parity_quick.py and
tests/conftest.py before any program compiles.
"""
from __future__ import annotations

import json
import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    # cache every program: the tiny-model test programs and the sampler
    # tails compile in well under the 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return placed or DEFAULT_CACHE_DIR


def device_tag(engine) -> str:
    """READY-line suffix `` device={json}`` naming the platform,
    device_kind and mesh devices of a NativeEngine (engine.device_info),
    given the engine or a worker wrapping it as ``.engine``; empty for
    anything that holds no device (echo engines, host-only services).
    Readers (chip_smoke.py, tools/real_ckpt_e2e.py) learn the backend
    from the process that holds it instead of probing with a second one."""
    info = getattr(getattr(engine, "engine", engine), "device_info", None)
    return f" device={json.dumps(info())}" if info else ""


def read_device_tag(ready_line: str) -> dict:
    """The dict a READY line's `` device={json}`` suffix carries."""
    _, sep, tail = ready_line.partition(" device=")
    if not sep:
        raise ValueError(f"READY line names no device: {ready_line!r}")
    return json.loads(tail)
