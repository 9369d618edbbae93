"""LLM worker: serves a token-level engine over a runtime endpoint.

The reference attaches GPU engines as subprocess side-cars behind ZMQ
(reference: lib/llm/src/engines/, SURVEY.md §2.8); here the engine is
in-process JAX (`NativeEngineWorker`) or a deterministic no-TPU fake
(`EchoTokenEngine`, the analogue of the reference's EchoFull/EchoCore,
launch/dynamo-run/src/output/echo_*.rs). The wire contract both directions
is the common protocol: PreprocessedRequest in, EngineOutput frames out.

The worker also owns the router-facing side channels: KV events from its
page allocator and ForwardPassMetrics via the endpoint stats handler
(SURVEY.md §3.4).
"""
from __future__ import annotations

import asyncio
import dataclasses
import glob
import json
import logging
import os
import sys
import time
from typing import AsyncIterator, Dict, Optional

from jax.profiler import TraceAnnotation

from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
from dynamo_tpu.kv_router.publisher import KvEventPublisher, KvMetricsPublisher
from dynamo_tpu.protocols.common import (
    EngineOutput, FinishReason, PreprocessedRequest,
)
from dynamo_tpu.runtime.engine import AsyncEngine, Context
from dynamo_tpu.runtime.tracing import TRACER

log = logging.getLogger("dynamo_tpu.worker")


def _to_engine_request(pre: PreprocessedRequest, qos: str = "",
                       trace=None) -> EngineRequest:
    s, st, out = pre.sampling, pre.stop, pre.output
    # resume-from-prefix (mid-stream migration): token_ids already carries
    # prompt + committed tokens; the whole sequence re-prefills and decode
    # continues from there, so the committed tokens are charged against
    # the ORIGINAL stop budgets here. max(1, ...) is dead-man's defense —
    # the reliability layer never dispatches an exhausted budget.
    resume = pre.resume_committed or 0
    mm_pixels = None
    mm_spans = None
    if pre.mm_parts:
        import numpy as np
        mm_pixels, mm_spans = [], []
        for p in pre.mm_parts:
            arr = (np.frombuffer(p.data, dtype=np.dtype(p.dtype))
                   .reshape(p.shape).astype(np.float32))
            if p.kind == "embeds" and p.salt is not None:
                # pre-encoded patch embeds + transfer-invariant salt
                # (disagg mm_transfer="embeds"): no vision tower run here
                mm_spans.append((p.offset, arr, int(p.salt)))
            else:
                mm_pixels.append((p.offset, arr))
        mm_pixels = mm_pixels or None
        mm_spans = mm_spans or None
    return EngineRequest(
        request_id=pre.request_id,
        prompt=list(pre.token_ids),
        mm_pixels=mm_pixels,
        mm_spans=mm_spans,
        qos=qos,
        trace=trace,
        params=SamplingParams(
            max_tokens=max(1, (st.max_tokens or 16) - resume),
            temperature=s.temperature if s.temperature is not None else 0.0,
            top_k=s.top_k or 0,
            top_p=s.top_p if s.top_p is not None else 1.0,
            seed=s.seed or 0,
            ignore_eos=st.ignore_eos,
            stop_token_ids=tuple(st.stop_token_ids_hidden or ()),
            min_tokens=max(0, (st.min_tokens or 0) - resume),
            repetition_penalty=s.repetition_penalty or 1.0,
            logprobs=out.logprobs,
        ))


class EchoTokenEngine(AsyncEngine):
    """Echoes the prompt tokens back, one frame per token, rate-limited.

    Deterministic zero-hardware engine for tests and stack bring-up
    (reference: echo_full.rs / echo_core.rs).
    """

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s

    async def generate(self, request, context: Context):
        pre = PreprocessedRequest.model_validate(request)
        # resume-from-prefix: token_ids = original prompt + the committed
        # tokens a dead worker already streamed; for echo those committed
        # tokens are the prompt's own head, so the continuation restarts
        # mid-prompt and the budget charges what was already emitted
        resume = pre.resume_committed or 0
        prompt = pre.token_ids[:len(pre.token_ids) - resume] if resume \
            else pre.token_ids
        n = pre.stop.max_tokens or len(prompt)
        emitted = resume
        for tok in prompt[resume:]:
            if emitted >= n or context.is_stopped:
                break
            if self.delay_s:
                await asyncio.sleep(self.delay_s)
            emitted += 1
            yield EngineOutput(token_ids=[tok]).model_dump(exclude_none=True)
        reason = (FinishReason.LENGTH if emitted >= n
                  else FinishReason.CANCELLED if context.is_stopped
                  else FinishReason.STOP)
        yield EngineOutput(token_ids=[], finish_reason=reason).model_dump(
            exclude_none=True)


class NativeEngineWorker(AsyncEngine):
    """Serves a NativeEngine: async request fan-in, device step loop,
    per-request frame fan-out, KV event + metrics publication."""

    def __init__(self, engine, component=None, worker_id: str = "",
                 step_idle_sleep_s: float = 0.002):
        self.engine = engine
        self.worker_id = worker_id
        self._component = component
        self.metrics_publisher = KvMetricsPublisher()
        self.event_publisher = (
            KvEventPublisher(component, worker_id) if component is not None
            else None)
        # shared-pool event publisher (engine/kv_pool.py): created lazily
        # once the engine has a pool attached — pool Stored/Removed events
        # ride the same plane under the `pool:{worker_id}` source id so
        # the router indexer learns pool-resident prefixes
        self._pool_publisher = None
        self._queues: Dict[str, asyncio.Queue] = {}
        self._wake = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._idle_sleep = step_idle_sleep_s
        # engine state is touched ONLY by the step loop (adds/aborts are
        # staged here) so nothing mutates the scheduler while a device step
        # runs in the executor thread
        self._pending_adds: list = []
        self._pending_aborts: list = []
        # arbitrary staged engine ops (disagg page inject/extract/activate);
        # run FIFO between device steps
        self._pending_ops: list = []
        # a bounded profiler capture in progress (capture_profile): the
        # JAX trace is process-global, so one at a time
        self._capturing = False

    def submit(self, fn) -> asyncio.Future:
        """Stage `fn(engine)` to run between device steps; returns a future
        resolving to its result. The only safe way to touch engine state
        from outside the step loop."""
        fut = asyncio.get_running_loop().create_future()
        self._pending_ops.append((fn, fut))
        self._wake.set()
        return fut

    async def start(self) -> "NativeEngineWorker":
        self._loop_task = asyncio.create_task(self._step_loop())
        return self

    async def capture_profile(self, seconds: float, out_dir: str) -> dict:
        """One bounded JAX profiler capture of the serving loop, and its
        table: start, sleep `seconds`, stop (in the executor: stopping
        serialises the trace), Python tracer off (it slows the host it
        measures); then `python -m dynamo_tpu.observability.profile
        <out_dir>` in a CHILD process, which reads the xplane alone and
        writes `profile_summary.json` beside it: neither the engine's
        thread nor the event loop waits on the reduction. Refuses while
        one runs, here or anywhere else in the process (the JAX trace is
        process-global). The capture holds the engine's `engine.<phase>`
        and the loop's `worker.*` annotations next to the device's lines,
        each `engine.dispatch` with what it launched as its stats. Beside
        the `*.xplane.pb` it leaves `programs/*.hlo.txt`, the optimised
        HLO of the programs the engine launched meanwhile
        (NativeEngine.program_texts): a trace's ops name no scope, their
        `op_name`s there do. Returns {"trace_dir", "summary": the file's
        path (None where the child failed), the summary's top level, and
        "cost_s": what stopping, the programs' texts and the child took}."""
        if self._capturing:
            raise RuntimeError("a profiler capture is already running")
        import jax
        self._capturing = True
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            since = getattr(self.engine, "_dispatch_seq", 0)
            jax.profiler.start_trace(out_dir, profiler_options=opts)
            cost = {}
            try:
                await asyncio.sleep(seconds)
            finally:
                await asyncio.get_running_loop().run_in_executor(
                    None, self._stop_capture, out_dir, since, cost)
            log.info("jax profiler: %.1fs captured to %s (stop %.1fs, the "
                     "programs' HLO %.2fs)", seconds, out_dir,
                     cost.get("stop", 0.0), cost.get("programs", 0.0))
            out = {"trace_dir": out_dir, "summary": None, "cost_s": cost}
            t_child = time.perf_counter()
            import dynamo_tpu
            root = os.path.dirname(os.path.dirname(dynamo_tpu.__file__))
            child = await asyncio.create_subprocess_exec(
                sys.executable, "-m", "dynamo_tpu.observability.profile",
                out_dir, stdout=asyncio.subprocess.PIPE,
                # the child reads a file: it is never to reach for a chip
                env={**os.environ, "JAX_PLATFORMS": "cpu",
                     "PYTHONPATH": os.pathsep.join(filter(None, (
                         root, os.environ.get("PYTHONPATH"))))})
            stdout, _ = await child.communicate()
            cost["child"] = time.perf_counter() - t_child
            if child.returncode == 0:
                out.update(json.loads(stdout.splitlines()[-1]))
            else:
                log.error("no summary of %s: the reducer exited %d",
                          out_dir, child.returncode)
            return out
        finally:
            self._capturing = False

    def _stop_capture(self, out_dir: str, since: int, cost: dict) -> None:
        import jax
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        cost["stop"] = t1 - t0
        texts = getattr(self.engine, "program_texts", None)
        try:
            path = sorted(glob.glob(os.path.join(
                out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
            home = os.path.join(os.path.dirname(path), "programs")
            os.makedirs(home, exist_ok=True)
            for name, text in (texts(since) if texts else {}).items():
                with open(os.path.join(home, name + ".hlo.txt"), "w") as f:
                    f.write(text)
            cost["programs"] = time.perf_counter() - t1
        except Exception:  # the capture itself is whole without them
            log.exception("no programs beside the capture")

    async def stop(self) -> None:
        if self._loop_task:
            self._loop_task.cancel()
            try:
                await self._loop_task
            except asyncio.CancelledError:
                pass
            self._loop_task = None
        close = getattr(self.engine, "close", None)
        if close:
            close()

    # -- engine loop ----------------------------------------------------------

    def _apply_pending(self) -> None:
        """Apply staged ops/adds/aborts; runs only between device steps."""
        ops, self._pending_ops = self._pending_ops, []
        for fn, fut in ops:
            try:
                result = fn(self.engine)
            except Exception as e:  # surface to the submitter
                if not fut.done():
                    fut.set_exception(e)
            else:
                if not fut.done():
                    fut.set_result(result)
        adds, self._pending_adds = self._pending_adds, []
        for req in adds:
            try:
                self.engine.add_request(req)
            except (ValueError, MemoryError) as e:
                q = self._queues.get(req.request_id)
                if q is not None:
                    # ValueError = deterministic request rejection (OOV id,
                    # over max_model_len): not retryable elsewhere.
                    # MemoryError = THIS worker is out of capacity: another
                    # instance may well take it.
                    q.put_nowait(EngineOutput(
                        finish_reason=FinishReason.ERROR, text=str(e),
                        retryable=isinstance(e, MemoryError)))
        aborts, self._pending_aborts = self._pending_aborts, []
        for rid in aborts:
            self.engine.abort(rid)

    async def _step_loop(self) -> None:
        loop = asyncio.get_running_loop()
        # perf_counter marks of the time since the last step() returned:
        # this coroutine running again, and the end of the loop body
        t_resumed = t_emitted = 0.0
        while True:
            # the synchronous stretches between two steps are annotated for
            # a profiler capture, never across an await
            with TraceAnnotation("worker.apply_pending"):
                self._apply_pending()
            t_applied = time.perf_counter()
            if not self.engine.has_work():
                self._wake.clear()
                if not self._pending_adds and not self._pending_ops:
                    self.metrics_publisher.update(self.engine.metrics())
                    # sleeping is idleness, not host time between steps
                    self.engine.note_idle()
                    try:
                        await asyncio.wait_for(self._wake.wait(), timeout=1.0)
                    except asyncio.TimeoutError:
                        pass
                continue
            # the engine splits the time between two steps at the three
            # marks (llm_engine_host_resume_ / _emit_ / _apply_pending_ /
            # _submit_seconds); after a sleep it charges none (note_idle)
            self.engine.note_between(t_resumed, t_emitted, t_applied)
            try:
                outputs = await loop.run_in_executor(None, self.engine.step)
            except Exception:
                log.exception("engine step failed; failing active requests")
                for q in self._queues.values():
                    q.put_nowait(EngineOutput(
                        finish_reason=FinishReason.ERROR, retryable=True))
                self._queues.clear()
                # requests staged during the failing step have no consumer
                # anymore — drop them so they never occupy an engine slot
                self._pending_adds.clear()
                t_resumed = t_emitted = time.perf_counter()
                continue
            t_resumed = time.perf_counter()
            # frame fan-out and the metrics snapshot, on the event loop
            # that also serves the sockets
            with TraceAnnotation("worker.emit"):
                for ev in outputs:
                    q = self._queues.get(ev.request_id)
                    if q is None:
                        continue
                    q.put_nowait(EngineOutput(
                        token_ids=([ev.token] if ev.token is not None
                                   else []),
                        log_probs=([ev.logprob] if ev.logprob is not None
                                   else None),
                        top_logprobs=([[[float(t), lp] for t, lp in
                                        ev.top_logprobs]]
                                      if ev.top_logprobs is not None
                                      else None),
                        finish_reason=(FinishReason(ev.finish_reason)
                                       if ev.finish_reason else None)))
                self.metrics_publisher.update(self.engine.metrics())
            pool = getattr(self.engine, "kv_pool", None)
            if self.event_publisher is not None or pool is not None:
                # the drain also tees sealed pages into the shared pool
                # (engine._publish_pool_pages), so it runs whenever a
                # pool is attached even without a router event plane
                events = self.engine.drain_kv_events()
                if self.event_publisher is not None and events:
                    await self.event_publisher.publish_allocator_events(events)
            if pool is not None and self._component is not None:
                if self._pool_publisher is None:
                    from dynamo_tpu.kv_router.protocols import pool_source_id
                    self._pool_publisher = KvEventPublisher(
                        self._component, pool_source_id(self.worker_id))
                pev = pool.drain_events(self.engine.kv_pool_source)
                if pev:
                    await self._pool_publisher.publish_allocator_events(pev)
            t_emitted = time.perf_counter()

    # -- AsyncEngine ----------------------------------------------------------

    def _register(self, request_id: str) -> asyncio.Queue:
        q: asyncio.Queue = asyncio.Queue()
        self._queues[request_id] = q
        return q

    async def _stream(self, request_id: str, context: Context,
                      q: asyncio.Queue):
        """Drain a request's frame queue, honoring client-side stop."""
        stop = asyncio.create_task(context.wait_stopped())
        get = None
        trace = context.trace
        try:
            while True:
                get = asyncio.create_task(q.get())
                done, _ = await asyncio.wait(
                    {get, stop}, return_when=asyncio.FIRST_COMPLETED)
                if stop in done and get not in done:
                    # cancel + clear `get` so the finally block doesn't
                    # stage a duplicate abort for this request
                    get.cancel()
                    get = None
                    self._pending_aborts.append(request_id)
                    self._wake.set()
                    yield EngineOutput(
                        finish_reason=FinishReason.CANCELLED).model_dump(
                            exclude_none=True)
                    return
                frame: EngineOutput = get.result()
                get = None
                if frame.token_ids:
                    # per-emit instant: trace_explain derives per-window
                    # decode ITL from the gaps between these (one branch
                    # when tracing is off)
                    TRACER.event("decode.emit", trace,
                                 n=len(frame.token_ids))
                yield frame.model_dump(exclude_none=True)
                if frame.finish_reason is not None:
                    return
        finally:
            stop.cancel()
            if get is not None:  # client closed the stream mid-get
                get.cancel()
                self._pending_aborts.append(request_id)
                self._wake.set()

    async def generate(self, request, context: Context):
        pre = PreprocessedRequest.model_validate(request)
        if pre.request_id in self._queues:
            # a second dispatch of a live id would CLOBBER the first
            # stream's frame queue (plain dict assignment in _register),
            # starving it — reject before touching the registry. The
            # engine's admission guard (scheduler._admit) is the backstop;
            # this keeps the first stream intact too.
            yield EngineOutput(
                finish_reason=FinishReason.ERROR, retryable=False,
                text=f"request {pre.request_id} already in flight on this "
                     "worker").model_dump(exclude_none=True)
            return
        q = self._register(pre.request_id)
        try:
            # QoS class rides Context.baggage across the wire (the
            # trace-context pattern, runtime/qos.py): the engine
            # scheduler orders its waiting queue and selects preemption
            # victims by it
            from dynamo_tpu.runtime.qos import qos_of
            self._pending_adds.append(_to_engine_request(
                pre, qos=qos_of(context.baggage), trace=context.trace))
            self._wake.set()
            async for frame in self._stream(pre.request_id, context, q):
                yield frame
        finally:
            self._queues.pop(pre.request_id, None)

    # -- stats ----------------------------------------------------------------

    def stats_handler(self) -> dict:
        return self.metrics_publisher.stats_handler()


async def serve_llm_worker(runtime, namespace: str, component: str,
                           engine: AsyncEngine, endpoint: str = "generate",
                           card=None, role: str = None):
    """Register + serve an LLM engine endpoint with stats wired up.

    Also wires the KV event publisher for engines that support one but
    weren't given a component at construction (NativeEngineWorker and
    subclasses built before the runtime existed — run.py endpoint mode,
    the SDK example workers). Without it a kv-routed frontend receives no
    overlap data from these workers and silently degrades to load
    balancing (found by tools/routing_ttft_bench.py: ~50% prefix hit
    instead of ~100%). The worker_id must be the runtime's — that is the
    instance id routers see in the event stream and the instance table.
    Reference analogue: workers construct their KvEventPublisher with
    their own worker id at startup (publisher.rs:33-74).
    """
    comp = runtime.namespace(namespace).component(component)
    ep = comp.endpoint(endpoint)
    if getattr(engine, "event_publisher", "absent") is None:
        engine.event_publisher = KvEventPublisher(comp, runtime.worker_id)
    stats = getattr(engine, "stats_handler", None)
    metadata = {"model_card": card.to_dict()} if card is not None else {}
    # serving role on the instance key (runtime/component.instance_role):
    # what `Client.ids_for_role`, the fleet rollup's per-role aggregates,
    # and the autoscaler's re-role actuation key on. Disagg engines
    # self-describe (DisaggDecodeWorker.serving_role); aggregated
    # engines stay role-less wildcards.
    role = role if role is not None else getattr(engine, "serving_role",
                                                 None)
    if role is not None:
        metadata["role"] = role
    served = await ep.serve(engine, metadata=metadata or None,
                            stats_handler=stats)
    return served


def install_graceful_drain(runtime, served, timeout_s: float = None) -> None:
    """SIGTERM/SIGINT -> graceful drain for a serving worker process:
    mark the instance DRAINING first (routers and the kv_router fence it
    out of NEW assignments while the request subject stays up), let
    in-flight response streams finish (bounded by DYN_DRAIN_TIMEOUT_S,
    default 30 s), cut whatever is left (those streams migrate through
    the reliability layer, token-identical), deregister, then shut the
    runtime down so the process exits cleanly. This is one leg of a
    zero-drop rolling restart (docs/RESILIENCE.md runbook).

    The reference couples SIGTERM to its runtime cancellation token and
    drains endpoints the same way (graceful shutdown for k8s rolling
    restarts); without this, a SIGTERM kills mid-stream responses.
    Installed by `dynamo_tpu.run in=endpoint` (worker mode); any embedder
    of serve_llm_worker can call it too.
    """
    import os
    import signal as _signal

    if timeout_s is None:
        timeout_s = float(os.environ.get("DYN_DRAIN_TIMEOUT_S", "30"))
    loop = asyncio.get_running_loop()
    # the loop holds only weak task refs: an unreferenced drain task can
    # be garbage-collected mid-await — keep it here. "force" lets a
    # SECOND signal skip the in-flight wait (operator escalation).
    state = {"task": None, "force": False}

    async def drain():
        log.warning("SIGTERM: draining — fencing instance, then up to "
                    "%.0fs for %d in-flight stream(s)", timeout_s,
                    len(served.inflight))
        try:
            await served.drain(timeout_s=timeout_s, poll_s=0.2,
                               force=lambda: state["force"])
        except Exception:  # noqa: BLE001 — exit cleanly regardless
            log.exception("drain failed; shutting down anyway")
        await runtime.shutdown()

    def on_signal():
        if state["task"] is None:
            state["task"] = asyncio.ensure_future(drain())
        else:
            state["force"] = True  # escalate: stop waiting on streams

    for sig in (_signal.SIGTERM, _signal.SIGINT):
        try:
            loop.add_signal_handler(sig, on_signal)
        except (NotImplementedError, RuntimeError):  # pragma: no cover
            pass  # non-main thread / platform without signal support
