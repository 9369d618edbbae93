"""SDK layer tests: decorators/graph collection in-process, then a real
multi-process launch via the supervisor (control-plane server + one process
per service), driven by a runtime client — the reference's `dynamo serve`
flow (SURVEY.md §3.5) end to end.
"""
import asyncio
import json
import os
import signal
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_graph_collection_order():
    from tests.sdk_graph import EchoWorker, Processor
    from dynamo_tpu.sdk.service import collect_graph

    specs = collect_graph(Processor)
    assert [s.name for s in specs] == ["EchoWorker", "Processor"]
    proc = Processor.__service_spec__
    assert proc.dependencies == {"worker": EchoWorker}
    assert proc.endpoints == {"generate": "generate"}
    assert EchoWorker.__service_spec__.start_hooks == ["boot"]


def test_chip_allocator():
    from dynamo_tpu.sdk.allocator import ChipAllocator

    alloc = ChipAllocator(4)
    assert alloc.env_for({}) == {"JAX_PLATFORMS": "cpu"}
    env = alloc.env_for({"tpu": 2})
    assert env["TPU_VISIBLE_CHIPS"] == "0,1"
    assert alloc.env_for({"tpu": 1})["TPU_VISIBLE_CHIPS"] == "2"
    with pytest.raises(RuntimeError, match="not enough"):
        alloc.env_for({"tpu": 2})


def test_sdk_graph_multiprocess_roundtrip(tmp_path):
    port = free_port()
    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps({"EchoWorker": {"prefix": ">"}}))
    sup = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.sdk.serve",
         "tests.sdk_graph:Processor", "-f", str(cfg),
         "--start-control-plane", "--control-port", str(port)],
        stdout=subprocess.PIPE, cwd=REPO, env=ENV, text=True)
    try:
        deadline = 90
        while True:
            line = sup.stdout.readline()
            assert line, "supervisor exited early"
            if line.startswith("READY graph="):
                break

        async def drive():
            from dynamo_tpu.runtime.distributed import DistributedRuntime
            rt = await DistributedRuntime.connect("127.0.0.1", port)
            client = rt.namespace("sdktest").component(
                "processor").endpoint("generate").client()
            await client.start()
            await client.wait_for_instances()
            frames = []
            async for f in await client.generate({"text": "hello tpu"}):
                frames.append(f)
            await client.stop()
            await rt.shutdown()
            return frames

        frames = asyncio.run(asyncio.wait_for(drive(), deadline))
        assert frames == [{"word": ">HELLO"}, {"word": ">TPU"},
                          {"count": 2}]
    finally:
        sup.send_signal(signal.SIGINT)
        try:
            sup.wait(15)
        except subprocess.TimeoutExpired:
            sup.kill()
