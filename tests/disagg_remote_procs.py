"""Role scripts for the TRUE two-process disaggregation test.

Spawned by tests/test_remote_transfer.py with a shared standalone
control-plane server: one process runs the decode worker (+ KvTransferServer
registered in the discovery KV), the other runs the prefill worker (+
RemoteTransferBackend). KV pages cross a real process boundary over TCP —
the reference's NIXL role (SURVEY.md §2.7), exercised the way its disagg
example deploys (separate engine processes, examples/llm/graphs).

Usage: python tests/disagg_remote_procs.py {decode|prefill} <control_port>
"""
import asyncio
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.disagg import (  # noqa: E402
    DisaggDecodeWorker, DisaggregatedRouter, KvTransferServer, PrefillQueue,
    PrefillWorker, RemoteTransferBackend,
)
from dynamo_tpu.engine.config import EngineConfig, ModelConfig  # noqa: E402
from dynamo_tpu.engine.engine import NativeEngine  # noqa: E402
from dynamo_tpu.llm.worker import (  # noqa: E402
    NativeEngineWorker, serve_llm_worker,
)
from dynamo_tpu.parallel.mesh import make_mesh  # noqa: E402
from dynamo_tpu.runtime.distributed import DistributedRuntime  # noqa: E402

CFG = ModelConfig(dtype="float32", max_model_len=512)
PAGE = 8


def make_engine(mesh=None):
    return NativeEngine(CFG, EngineConfig(
        page_size=PAGE, num_pages=64, max_slots=4, max_prefill_chunk=32,
        prefill_buckets=(8, 16, 32), max_model_len=512), mesh=mesh, seed=0)


async def decode_main(port: int) -> None:
    rt = await DistributedRuntime.connect("127.0.0.1", port,
                                          worker_id="dec-0")
    queue = PrefillQueue(rt.messaging, "ns", "tiny")
    router = DisaggregatedRouter(max_local_prefill_length=4,
                                 max_prefill_queue_size=8, model="tiny")
    worker = DisaggDecodeWorker(
        make_engine(), rt.messaging, router, queue,
        worker_id="dec-0", prefill_timeout_s=60.0)
    await worker.start()
    server = await KvTransferServer(worker, "dec-0").start()
    await server.register(rt.kv, rt.lease.id)
    await serve_llm_worker(rt, "ns", "decoder", worker)
    print("READY decode", flush=True)
    await rt.shutdown_event.wait()


async def prefill_main(port: int) -> None:
    rt = await DistributedRuntime.connect("127.0.0.1", port,
                                          worker_id="pre-0")
    queue = PrefillQueue(rt.messaging, "ns", "tiny")
    # tp=2 mesh: the prefill cache layout differs from decode's tp=1 —
    # the transfer's device_put reshard covers the kv_rearrange role
    mesh = make_mesh(tp=2, devices=jax.devices()[:2])
    transfer = RemoteTransferBackend(rt.kv, chunk_pages=2)
    worker = PrefillWorker(NativeEngineWorker(make_engine(mesh)), queue,
                           transfer, rt.messaging)
    await worker.start()
    print("READY prefill", flush=True)
    await rt.shutdown_event.wait()


if __name__ == "__main__":
    role, port = sys.argv[1], int(sys.argv[2])
    main = decode_main if role == "decode" else prefill_main
    try:
        asyncio.run(main(port))
    except KeyboardInterrupt:
        pass
