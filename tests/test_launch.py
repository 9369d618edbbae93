"""What the launchers do around an engine so that it runs on the chip it was
given (PR 21): one compile cache placeable from outside, chips handed only
to the services that own an engine, parents that never take the backend,
an explicit kernel request that is refused rather than replaced, and READY
lines that name the device."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_updates(monkeypatch):
    """Record what enable_compile_cache() sets through jax.config.update
    (the config is process-global; the suite's own cache is left alone)."""
    import jax
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.__setitem__(name, value))
    from dynamo_tpu.utils.launch import enable_compile_cache
    return calls, enable_compile_cache()


def test_compile_cache_follows_the_environment_when_it_is_set(
        monkeypatch, tmp_path):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    calls, ret = _config_updates(monkeypatch)
    # JAX reads the variable itself: no directory is set in code
    assert "jax_compilation_cache_dir" not in calls
    assert ret == placed


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls, ret = _config_updates(monkeypatch)
    assert calls["jax_compilation_cache_dir"] == ret \
        == os.path.join(REPO, ".jax_cache")


def test_allocator_never_sends_an_engine_service_to_the_cpu():
    from dynamo_tpu.sdk.allocator import CHIP_BOUNDS, ChipAllocator
    from dynamo_tpu.sdk.service import collect_graph
    from examples.disagg.graph import Frontend

    specs = {s.name: s for s in collect_graph(Frontend)}
    assert specs["PrefillWorker"].resources == {"tpu": 1}
    assert specs["DecodeWorker"].resources == {"tpu": 1}
    assert not specs["Frontend"].resources.get("tpu")

    alloc = ChipAllocator(4)
    seen = []
    for name in ("PrefillWorker", "DecodeWorker"):
        env = alloc.env_for(specs[name].resources)
        assert "JAX_PLATFORMS" not in env
        assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == CHIP_BOUNDS[1]
        assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        seen.append(env["TPU_VISIBLE_CHIPS"])
    assert seen == ["0", "1"]                      # disjoint chips
    # host-only service: kept off the chips
    assert alloc.env_for(specs["Frontend"].resources) == {
        "JAX_PLATFORMS": "cpu"}
    # a 2-chip block starts on an even chip (one row of the 2x2 host)
    assert alloc.env_for({"tpu": 2})["TPU_VISIBLE_CHIPS"] == "2,3"
    with pytest.raises(RuntimeError, match="not enough TPU chips"):
        alloc.env_for({"tpu": 1})
    with pytest.raises(RuntimeError, match="no chip layout"):
        ChipAllocator(8).env_for({"tpu": 3})
    # the default --tpu-chips 0 on a TPU host: an error, not a silent CPU
    with pytest.raises(RuntimeError, match="not enough TPU chips"):
        ChipAllocator(0).env_for({"tpu": 1})


def test_operator_cpu_environment_runs_the_graph_on_the_cpu():
    """JAX_PLATFORMS=cpu in the operator's own environment (demos, this
    suite) is the one way an engine service lands on the CPU: it inherits
    that, and the allocator assigns nothing."""
    from dynamo_tpu.sdk.allocator import ChipAllocator
    alloc = ChipAllocator(0, host_is_cpu=True)
    assert alloc.env_for({"tpu": 1}) == {}
    assert alloc.env_for({}) == {"JAX_PLATFORMS": "cpu"}


def test_importing_the_launchers_initialises_no_backend():
    """sdk.serve and chip_smoke.py are parents of engine processes: a
    parent that initialised a backend would hold the chip its children
    need. Importing the launchers and the example graph (which imports
    jax and the engine) must stay short of that."""
    code = ("import dynamo_tpu.sdk.serve, dynamo_tpu.run, "
            "examples.disagg.graph\n"
            "from jax._src import xla_bridge\n"
            "print('BACKENDS', xla_bridge.backends_are_initialized())\n")
    env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKENDS False" in out.stdout


def test_decode_kernel_on_is_refused_not_replaced():
    """decode_kernel='on' where the compiled kernel cannot serve raises at
    engine construction; it used to log a line and serve the gather path
    under the kernel's label."""
    import dataclasses

    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.paged_attention import kernel_supported

    on = ModelConfig(decode_kernel="on", head_dim=48)
    assert not kernel_supported(on.head_dim, 16)
    with pytest.raises(ValueError, match="no tile-aligned DMA path"):
        NativeEngine(on, EngineConfig(page_size=16, num_pages=8))
    softcapped = dataclasses.replace(ModelConfig(), decode_kernel="on",
                                     attn_softcap=50.0)
    with pytest.raises(ValueError, match="no hooks"):
        NativeEngine(softcapped, EngineConfig(page_size=16, num_pages=8))
    # "auto" keeps meaning the gather path, on every platform
    assert llama._decode_kernel_mode(ModelConfig()) is None


def test_ready_line_names_the_engine_devices():
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.llm.worker import EchoTokenEngine
    from dynamo_tpu.utils.launch import device_tag

    engine = NativeEngine(ModelConfig(dtype="float32"),
                          EngineConfig(page_size=16, num_pages=8))
    tag = device_tag(engine)
    assert tag.startswith(" device=")
    info = json.loads(tag[len(" device="):])
    assert info["platform"] == "cpu" and info["devices"] == [0]
    assert info["device_kind"] and info["mesh"] == {}
    assert device_tag(EchoTokenEngine()) == ""
