"""Canonical disaggregated serving graph (SDK), the reference's L6 role.

Reference equivalent: examples/llm/graphs/disagg_router.py:16-22 — the
Frontend -> Processor -> Router -> VllmWorker -> PrefillWorker chain. Here
the Processor and Router roles live inside the Frontend process: the model
watcher builds the preprocess -> KV-router -> worker pipeline per registered
model (dynamo_tpu/frontend/discovery.py), which is the same split the
reference's standalone http binary uses (components/http/src/main.rs).

Services:
- Frontend        OpenAI HTTP + model discovery + KV-aware routing
- DecodeWorker    DisaggDecodeWorker + KvTransferServer (NIXL-server role)
                  + model registration
- PrefillWorker   queue consumer + RemoteTransferBackend (NIXL-client role)

Each engine-owning service declares resources={"tpu": 1}: the supervisor
(sdk/serve.py) hands it one chip of --tpu-chips and it is the only process
that touches that chip; the Frontend is host-only.

Run on a TPU host (run on a four-chip v5e by chip_smoke.py's `disagg`
phase — llama3-1b, one chip for the prefill engine, one for the decode
engine):
  python -m dynamo_tpu.sdk.serve examples.disagg.graph:Frontend \
      -f examples/disagg/config.yaml --start-control-plane --tpu-chips 2

Run on the CPU (tiny model, one command; JAX_PLATFORMS=cpu is what keeps
the engines off the chips):
  JAX_PLATFORMS=cpu python -m dynamo_tpu.sdk.serve \
      examples.disagg.graph:Frontend \
      -f examples/disagg/config.cpu.yaml --start-control-plane

then:
  curl -N localhost:8099/v1/chat/completions -H 'Content-Type: application/json' \
    -d '{"model": "tiny", "stream": true, "max_tokens": 16, \
         "messages": [{"role": "user", "content": "hello"}]}'
"""
from __future__ import annotations

import asyncio

from dynamo_tpu.disagg import (
    DisaggDecodeWorker, DisaggregatedRouter, KvTransferServer, PrefillQueue,
    RemoteTransferBackend, ShardedKvTransferGroup,
)
from dynamo_tpu.disagg import PrefillWorker as QueuePrefillWorker
from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.engine import NativeEngine
from dynamo_tpu.frontend.discovery import register_model
from dynamo_tpu.frontend.serve import run_frontend
from dynamo_tpu.llm.worker import NativeEngineWorker, serve_llm_worker
from dynamo_tpu.run import build_card
from dynamo_tpu.sdk import async_on_start, depends, service
from dynamo_tpu.sdk.config import ServiceConfig

NS = "dynamo-demo"


def _build(cfg: dict):
    """Model card + engine from one service's config section. Callers run
    it in a thread: on a chip it takes tens of seconds (backend start-up,
    weight init), and the service's event loop must keep renewing the
    runtime's 10 s lease meanwhile."""
    card = build_card(cfg.get("model", "tiny"))
    model_cfg = card.model_config()
    max_len = int(cfg.get("max_model_len",
                          min(card.context_length, model_cfg.max_model_len)))
    engine = NativeEngine(
        model_cfg,
        EngineConfig(
            page_size=int(cfg.get("page_size", 64)),  # reference KV block 64
            num_pages=int(cfg.get("num_pages", 128)),
            max_slots=int(cfg.get("max_slots", 4)),
            max_prefill_chunk=int(cfg.get("max_prefill_chunk", 512)),
            prefill_buckets=tuple(
                cfg.get("prefill_buckets", (16, 64, 256, 512))),
            max_model_len=max_len,
        ),
        eos_token_ids=set(card.eos_token_ids))
    return card, engine


@service(name="PrefillWorker", namespace=NS, component="prefill",
         resources={"tpu": 1})
class PrefillWorker:
    """Prefill engine consuming the durable queue; ships KV pages to the
    decode workers over the remote transfer plane."""

    @async_on_start
    async def boot(self):
        cfg = ServiceConfig.global_instance().for_service("PrefillWorker")
        # kept as self.engine: run_service's READY line names its devices
        card, self.engine = await asyncio.to_thread(_build, cfg)
        queue = PrefillQueue(self.runtime.messaging, NS, card.name)
        transfer = RemoteTransferBackend(self.runtime.kv)
        self.worker = await QueuePrefillWorker(
            NativeEngineWorker(self.engine), queue, transfer,
            self.runtime.messaging,
            max_inflight=int(cfg.get("max_inflight", 4))).start()


@service(name="DecodeWorker", namespace=NS, component="backend",
         resources={"tpu": 1})
class DecodeWorker:
    """Decode engine with conditional remote prefill + KV-injection server."""

    prefill = depends(PrefillWorker)  # start-order edge; coupled via queue

    @async_on_start
    async def boot(self):
        cfg = ServiceConfig.global_instance().for_service("DecodeWorker")
        card, self.engine = await asyncio.to_thread(_build, cfg)
        queue = PrefillQueue(self.runtime.messaging, NS, card.name)
        router = DisaggregatedRouter(
            # reference example values: threshold 10, queue gate 2
            # (examples/llm/configs/disagg_router.yaml:38-40)
            max_local_prefill_length=int(
                cfg.get("max_local_prefill_length", 10)),
            max_prefill_queue_size=int(
                cfg.get("max_prefill_queue_size", 2)),
            model=card.name)
        router.start_watching(self.runtime.kv)
        worker = DisaggDecodeWorker(
            self.engine, self.runtime.messaging, router, queue,
            worker_id=f"decode-{self.runtime.worker_id}",
            prefill_timeout_s=float(cfg.get("prefill_timeout_s", 120.0)))
        await worker.start()
        # sharded parallel transfer (PERF.md §3f): transfer_hosts > 1
        # runs per-host endpoints with one chunk-committed stream per
        # (cache shard, host) — on a real multi-host decode mesh each
        # host runs its own endpoint so aggregate transfer bandwidth
        # scales with host count; transfer_streams optionally overrides
        # the natural shard count (must divide num_kv_heads)
        hosts = int(cfg.get("transfer_hosts", 1))
        if hosts > 1:
            self.kv_server = await ShardedKvTransferGroup(
                worker, worker.engine_id, hosts=hosts,
                n_streams=int(cfg.get("transfer_streams", 0))).start()
        else:
            self.kv_server = await KvTransferServer(
                worker, worker.engine_id).start()
        await self.kv_server.register(self.runtime.kv, self.runtime.lease.id)
        await serve_llm_worker(self.runtime, NS, "backend", worker,
                               card=card)
        await register_model(self.runtime.kv, card.name, NS, "backend", card)
        self.worker = worker


@service(name="Frontend", namespace=NS, component="frontend")
class Frontend:
    """OpenAI HTTP frontend; Processor+Router roles run in-process via the
    model watcher's discovery-built pipeline."""

    decode = depends(DecodeWorker)  # start-order edge

    @async_on_start
    async def boot(self):
        cfg = ServiceConfig.global_instance().for_service("Frontend")
        self.http = await run_frontend(
            self.runtime, port=int(cfg.get("port", 8099)),
            kv_routing=bool(cfg.get("kv_routing", True)))
        print(f"FRONTEND http=:{self.http.port}", flush=True)
