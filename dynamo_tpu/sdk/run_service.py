"""Per-process service entry (reference: sdk cli/serve_dynamo.py:107-191).

Spawned by the supervisor (sdk/serve.py), one process per service worker:
connect the runtime, instantiate the service class, resolve depends() edges
to ServiceClients, run @async_on_start hooks, serve the endpoints, block.
"""
from __future__ import annotations

import argparse
import asyncio
import importlib
import logging
import sys

from dynamo_tpu.runtime.distributed import DistributedRuntime
from dynamo_tpu.sdk.client import ServiceClient
from dynamo_tpu.utils.launch import device_tag, enable_compile_cache

log = logging.getLogger("dynamo_tpu.sdk")


def resolve(spec_str: str):
    mod_name, _, cls_name = spec_str.partition(":")
    mod = importlib.import_module(mod_name)
    cls = getattr(mod, cls_name)
    if not hasattr(cls, "__service_spec__"):
        raise SystemExit(f"{spec_str} is not a @service class")
    return cls


async def serve_service(cls, runtime) -> None:
    spec = cls.__service_spec__
    inst = cls()
    # services get the cluster handle before hooks run (kv, messaging,
    # lease) — the reference injects the same via @dynamo_worker
    # (reference: cli/serve_dynamo.py:111-122)
    inst.runtime = runtime
    for attr, dep_cls in spec.dependencies.items():
        setattr(inst, attr,
                ServiceClient(runtime, dep_cls.__service_spec__))
    for hook in spec.start_hooks:
        await getattr(inst, hook)()
    comp = runtime.namespace(spec.namespace).component(spec.component)
    stats = getattr(inst, "stats_handler", None)
    for ep_name, attr in spec.endpoints.items():
        await comp.endpoint(ep_name).serve(
            getattr(inst, attr), stats_handler=stats)
    shutdown = getattr(inst, "shutdown", None)
    runtime._service_instance = inst  # keep alive
    # a service that built a NativeEngine keeps it as `self.engine`; its
    # READY line then names the devices that process holds
    print(f"READY service={spec.name} worker={runtime.worker_id}"
          f"{device_tag(getattr(inst, 'engine', None))}", flush=True)


async def amain() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("service", help="module.path:ClassName")
    p.add_argument("--control-host", default="127.0.0.1")
    p.add_argument("--control-port", type=int, default=5550)
    p.add_argument("--coordinator", default=None,
                   help="jax.distributed coordinator addr (host:port) for "
                        "engines spanning processes/hosts; defaults to "
                        "DYN_COORD_ADDR")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    args = p.parse_args()
    from dynamo_tpu.utils.logconfig import configure_logging
    configure_logging()
    # join the engine's multi-process mesh BEFORE any jax use (reference
    # role: Ray leader/follower bootstrap, engines/vllm/ray.rs; here
    # jax.distributed so one Mesh spans all the service's hosts)
    from dynamo_tpu.parallel.bootstrap import bootstrap_distributed
    bootstrap_distributed(args.coordinator, args.num_processes,
                          args.process_id)
    cls = resolve(args.service)
    if "jax" in sys.modules:
        # the graph module pulls in the engine: keep what it compiles, and
        # start the backend NOW, before the runtime takes its 10 s lease —
        # TPU client start-up holds the GIL for longer than that, so a
        # backend first touched inside a start hook (even from a thread)
        # starves the keepalive and the service loses its registration
        import jax
        enable_compile_cache()
        jax.devices()
    runtime = await DistributedRuntime.connect(
        args.control_host, args.control_port)
    await serve_service(cls, runtime)
    await runtime.shutdown_event.wait()


if __name__ == "__main__":
    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        sys.exit(0)
