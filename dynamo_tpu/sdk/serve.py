"""Graph supervisor: launch a service graph, one process per worker.

Reference equivalent: `dynamo serve graphs.disagg:Frontend -f config.yaml`
(reference: sdk cli/serving.py:118-224 building a circus arbiter with one
watcher per service; SURVEY.md §3.5). Here: resolve the depends() graph,
optionally start the control-plane server, spawn
`python -m dynamo_tpu.sdk.run_service` per worker with per-service env
(config JSON + chip assignment), supervise until a child dies or SIGINT.

This process never initialises a JAX backend (importing the graph module
imports jax, which is harmless; touching a device would take the chips
from the children): a chip belongs to one process at a time. Services
declaring resources={"tpu": n} get n of --tpu-chips each
(sdk/allocator.py); with JAX_PLATFORMS=cpu in the environment the whole
graph runs on the CPU instead.

Usage:
  python -m dynamo_tpu.sdk.serve my.graphs:Frontend -f config.yaml \
      --start-control-plane --control-port 5550 --tpu-chips 4
"""
from __future__ import annotations

import argparse
import asyncio
import logging
import os
import signal
import sys

from dynamo_tpu.sdk.allocator import ChipAllocator
from dynamo_tpu.sdk.config import ServiceConfig, load_config_file
from dynamo_tpu.sdk.run_service import resolve
from dynamo_tpu.sdk.service import collect_graph

log = logging.getLogger("dynamo_tpu.sdk.serve")

# strong refs to per-child stdout drain tasks (see wait_ready)
_drain_tasks: set = set()


async def wait_ready(proc: asyncio.subprocess.Process, tag: str,
                     timeout: float = 240.0) -> None:
    """Engine-building services compile XLA programs before READY; the
    timeout covers a cold first compile on a busy host."""
    async def pump():
        while True:
            line = await proc.stdout.readline()
            if not line:
                raise RuntimeError(f"{tag} exited before READY")
            sys.stdout.write(f"[{tag}] {line.decode()}")
            sys.stdout.flush()
            if line.startswith(b"READY"):
                return
    await asyncio.wait_for(pump(), timeout)
    # keep draining in the background so the child never blocks on stdout
    async def drain():
        while True:
            line = await proc.stdout.readline()
            if not line:
                return
            sys.stdout.write(f"[{tag}] {line.decode()}")
            sys.stdout.flush()
    # retain the task: the loop holds only a weak ref, and a GC'd drain
    # task would let a chatty child fill its pipe and hang the graph
    task = asyncio.create_task(drain())
    _drain_tasks.add(task)
    task.add_done_callback(_drain_tasks.discard)


async def amain() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("graph", help="module.path:RootServiceClass")
    p.add_argument("-f", "--config", default=None,
                   help="JSON/YAML config file keyed by service name")
    p.add_argument("--control-host", default="127.0.0.1")
    p.add_argument("--control-port", type=int, default=5550)
    p.add_argument("--start-control-plane", action="store_true")
    p.add_argument("--tpu-chips", type=int, default=0,
                   help="chips on this host to hand to resources={'tpu': n} "
                        "services; a graph that asks for more is an error "
                        "(unless JAX_PLATFORMS=cpu runs it on the CPU)")
    args = p.parse_args()
    from dynamo_tpu.utils.logconfig import configure_logging
    configure_logging()

    root = resolve(args.graph)
    specs = collect_graph(root)
    cfg = load_config_file(args.config) if args.config else {}
    alloc = ChipAllocator(
        args.tpu_chips,
        host_is_cpu=os.environ.get("JAX_PLATFORMS") == "cpu")

    procs: list = []

    async def spawn(cmd, tag, extra_env=None):
        env = {**os.environ, **(extra_env or {})}
        proc = await asyncio.create_subprocess_exec(
            sys.executable, *cmd, stdout=asyncio.subprocess.PIPE,
            stderr=None, env=env)
        procs.append((tag, proc))
        await wait_ready(proc, tag)
        return proc

    try:
        if args.start_control_plane:
            await spawn(["-m", "dynamo_tpu.runtime.transports.server",
                         "--port", str(args.control_port)], "control-plane")
        for spec in specs:
            mod, cls = spec.cls.__module__, spec.cls.__qualname__
            for i in range(spec.workers):
                extra = {**ServiceConfig.to_env(cfg),
                         **alloc.env_for(spec.resources)}
                await spawn(
                    ["-m", "dynamo_tpu.sdk.run_service", f"{mod}:{cls}",
                     "--control-host", args.control_host,
                     "--control-port", str(args.control_port)],
                    f"{spec.name}/{i}", extra)
        print(f"READY graph={args.graph} services="
              f"{','.join(s.name for s in specs)}", flush=True)

        # supervise: exit when any child dies
        waits = {asyncio.create_task(proc.wait()): tag
                 for tag, proc in procs}
        done, _ = await asyncio.wait(waits, return_when=asyncio.FIRST_COMPLETED)
        for d in done:
            log.error("service %s exited with %s", waits[d], d.result())
            raise SystemExit(1)
    finally:
        for _tag, proc in reversed(procs):
            if proc.returncode is None:
                proc.send_signal(signal.SIGTERM)
        for _tag, proc in procs:
            try:
                await asyncio.wait_for(proc.wait(), 10.0)
            except asyncio.TimeoutError:
                proc.kill()


if __name__ == "__main__":
    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        pass
