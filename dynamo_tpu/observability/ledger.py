"""Per-step engine resource ledger: what is the engine DOING, over time.

PR 8 answered "where did THIS request's time go" (runtime/tracing.py);
nothing answered "what is the engine doing" — KV page occupancy per
tier, bucket-ladder padding waste, recompiles, batch occupancy, queue
depth, instantaneous tok/s — every `/metrics` render was a
point-in-time gauge with no per-step substrate behind it. The ledger is
that substrate: a bounded ring of per-step samples recorded at the
engine's commit sites, drainable as JSONL (tools/artifacts.py policy)
and folded into `llm_engine_*` gauges on every /metrics surface.

Recording discipline (the R13 deferred-recorder contract, same as
runtime/tracing.py `defer_phase`):

- **no device syncs, ever**: every sample field comes from host-side
  scheduler/allocator state the commit path already holds (allocator
  free counts, plan array shapes, deque lengths) — the ledger never
  touches a jax array;
- **disabled path is branch-only**: `record_step()`, `split_between()`
  and `close_call()` are one `if` each when off (`DYN_LEDGER=0`), so
  the decode pipeline's hot-path region pays nothing and stays
  token-identical either way (it is token-identical
  with the ledger ON too — the ledger only reads, tested in
  tests/test_decode_pipeline.py);
- **bounded**: the ring overwrites oldest samples (`samples_dropped`
  counted), so a week of serving cannot grow memory.

The ledger is ON by default (like PhaseTimer): per step() call one list
append, ~30 plain attribute bumps and float adds, and per committed
stream one dict read and write and two adds — tens of microseconds
next to a forward pass (PERF.md section 6, PR 35 has the measurement).
`DYN_LEDGER=0` turns even that off.

A sample is the record of one `step()` CALL (ISSUE 35): the commit site
records the step it committed (`record_step`), and the engine closes the
call at `step()`'s end with the call's own clock (`close_call`). A call
that only primed or chained the decode pipeline and committed nothing
has a record too, of the kind it dispatched (`calls()` lists it;
`drain()`, the per-step export, keeps to the calls that committed).

Per-step sample schema (one JSONL record per step after `drain()`):
    {"ts", "dt", "kind", "rows", "rows_live", "tokens_useful",
     "tokens_padded", "kv_used", "kv_total", "host_used", "host_total",
     "disk_used", "disk_total", "waiting", "recompiles", "stream_hit",
     "stream_late", "stream_spilled", "stream_stalls", "tok_s",
     "dev_steps", "streams", "tokens", "bucket", "t_entry", "t_exit",
     "between", "resume", "emit", "apply_pending", "submit", "phases",
     "stall"}
`kind` is the step kind ("prefill" | "decode" | "mixed" | "spec" |
"stream" — the last is a tiered-KV streamed long-context step, whose
stream_* columns carry that step's window-pool prefetch deltas);
`tokens_padded` is the FULL bucket charge of the step ([Bb, Tb] or
window steps x slots) so padded - useful is the bucket-ladder waste,
attributable per step kind: the plan's grid, which attention and the
scheduler's budget pay. What the token-wise layers ran over is the
cumulative `tokens_dense` (LedgerStats), not a column of the sample.
`recompiles` counts NEW (program, bucket)
keys first seen at this step's dispatch (an XLA compile stall).
The call's fields: `dev_steps` device steps of the program it committed
(a window's rung, else 1; 0 where it committed none), `streams` that
got a token and `tokens` committed, `bucket` of its program (`[Bb, Tb]`
of an `_engine_step`, a window's rung), `t_entry` / `t_exit` of the
call (`time.perf_counter()`), `between` (the time since the call
before returned, while the engine had work) and its four parts as the
worker's loop marks them (`resume`, `emit`, `apply_pending`, `submit`:
llm/worker.py `_step_loop`), and `phases`, name -> [start, seconds] of
the PhaseTimer phases this call ran. `between` + `t_exit` - `t_entry`
is the call's PERIOD: over a busy stretch the periods tile the wall time.
`stall`: the call committed a step, first dispatched no program, and its
period passed STALL_PERIOD_S all the same (`period_stalls_total`).

docs/OBSERVABILITY.md §5 documents the gauge catalog and the fleet
rollup (observability/fleet.py) that consumes the per-worker fields.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional


class LedgerStats:
    """Process-local fold target for the `llm_engine_*` gauges.

    Same pattern as runtime/cpstats.py CP_STATS: plain numeric fields
    bumped at record time, folded into Prometheus gauges at /metrics
    render by frontend/service.py. Values are process-local and
    last-writer-wins across engines in one process (cumulative fields
    add across engines) — the per-instance question /metrics answers.
    """

    FIELDS = (
        "steps_total",            # device steps committed (all kinds)
        "steps_prefill",          # pure prefill steps
        "steps_decode",           # decode windows (one per window)
        "steps_mixed",            # fused prefill+decode steps
        "steps_spec",             # speculative verify steps
        "steps_stream",           # tiered-KV streamed long-context steps
        "recompiles",             # new (program, bucket) keys dispatched
        "tokens_useful",          # committed/consumed tokens, all kinds
        "tokens_padded",          # full bucket charge, all kinds
        "tokens_dense",           # token rows the token-wise layers ran
        #                           over: a compact step's flat width
        #                           (models/llama.forward), else the charge
        "compact_steps_total",    # steps that took the compact branch
        "attn_split_steps_total",  # of the compact steps, those whose
        #                           attention ran over their real queries,
        #                           a row's last beside the chunk rows'
        #                           own (ops/attention.attention_rows):
        #                           the shapes where attention_rows_pay
        "useful_tokens_prefill",  # per-kind padding-waste split:
        "padded_tokens_prefill",  # prefill chunk rows
        "useful_tokens_decode",   # decode window (steps x slots)
        "padded_tokens_decode",
        "useful_tokens_mixed",    # fused steps ([Bb, Tb] charge)
        "padded_tokens_mixed",
        "kv_pages_used",          # HBM KV tier occupancy (pages)
        "kv_pages_total",
        "host_pages_used",        # host-DRAM offload tier occupancy
        "host_pages_total",
        "disk_pages_used",        # disk offload tier occupancy
        "disk_pages_total",
        "batch_rows_live",        # last step: live rows in the bucket
        "batch_rows_total",       # last step: bucket row capacity
        # tiered-KV streaming decode (engine/streaming.py), cumulative
        # across streamed steps: window-pool segments consumed from a
        # prior prefetch vs staged synchronously (the double-buffer's
        # hide-the-tier-latency verdict), pages spilled by the EWMA
        # policy, and steps that stalled on >= 1 late segment
        "stream_prefetch_hit",
        "stream_prefetch_late",
        "stream_pages_spilled",
        "stream_stall_steps",
        "queue_depth",            # last step: requests waiting
        "tok_s",                  # EWMA instantaneous useful tok/s
        "samples_dropped",        # ring overwrites (oldest lost)
        # the engine host loop's own clock (observability/metrics.py
        # PhaseTimer), cumulative seconds as floats, all step kinds:
        "host_plan_seconds",      # schedule + offload/onboard/pool work
        "host_upload_seconds",    # sampling arrays + host->device staging
        "host_dispatch_seconds",  # inside the jit call (first dispatches too)
        "host_wait_seconds",      # blocked in device_get/block_until_ready
        "host_commit_seconds",    # commits, postprocess, events, ledger
        "host_between_seconds",   # step() return -> next step() entry,
        #                           while the engine had work
        "host_exposed_seconds",   # of the above, the part spent with no
        #                           dispatched-and-unfetched program: the
        #                           host's estimate of device idle it causes
        "host_buffers_total",     # host->device buffers the step path staged
        #                           (engine._stage_operands): per step 1, a
        #                           fresh window 2, a chained window 0
        # what XLA really built or loaded in this process (jax.monitoring,
        # install_jax_listeners): every jit, not only the engine's keys
        "jax_compiles",           # backend compiles + persistent-cache loads
        "jax_compile_seconds",    # wall time inside them
        "jax_cache_hits",         # of those, loads from the persistent cache
        # MoE dispatch (ops/moe.py moe_stats), cumulative over every layer
        # call of every step, from the aux a step's outputs already carry:
        "moe_routed_total",       # (token, expert) assignments of real tokens
        "moe_dropped_total",      # of those, lost over an expert's capacity
        "moe_expert_rows_total",  # rows the expert matmuls computed, padding
        #                           and tile rounding included
        "moe_experts_hit_total",  # experts with >= 1 assignment, per call
        "moe_layer_calls_total",  # layer calls the above were summed over
        "moe_window_experts_hit_total",  # the same two over the layer
        "moe_window_layer_calls_total",  # calls of decode windows alone
        # attention's KV reads, from every step's plan on the host
        # (engine._account_attention), every model:
        "attn_kv_tokens_total",   # context tokens the real rows attend to
        "attn_kv_slots_total",    # token slots the gather path reads for
        #                           them: rows x table width x page size
        "kv_bytes_per_token",     # gauge: bytes a token holds in the
        #                           cache, all layers (set at engine start)
        "kv_heads_per_row",       # gauge: KV heads that share one row of
        #                           the device pool (engine/config.
        #                           kv_heads_per_row; 1 = a head a row)
        "kv_row_lanes",           # gauge: lanes that row is STORED in
        #                           (engine/config.kv_row_lanes: heads x
        #                           head_dim; a latent row's 576 values
        #                           in 640). kv_bytes_per_token stays the
        #                           MODEL's bytes, pad lanes not counted
        # a model whose sliding layers keep a page pool of their own
        # (ModelConfig.window_pool; engine._account_attention,
        # _account_window_pool). The two series above then count the
        # FULL pool's tables alone; all of these are 0 on other models
        "attn_kv_window_tokens_total",  # keys the real rows can see in a
        #                                 sliding layer (<= the window)
        "attn_kv_window_slots_total",   # token slots the window layers'
        #                                 gather reads for them
        "kv_bytes_per_token_full",    # gauge: bytes a token holds in the
        #                               full pool, its layers together
        "kv_bytes_per_token_window",  # gauge: the same in the window pool
        "kv_window_pages_held",       # gauge: pages a live row of the
        #                               last planned step holds there, mean
        "kv_window_pages_held_sum_total",  # the same summed over every
        "kv_window_rows_total",            # planned step, and its rows
        "kv_window_pages_released_total",  # pages handed back before
        #                               their sequence ended
        "kv_window_pages_used",       # gauge: the window pool's usage,
        "kv_window_pages_total",      # gauge: and its size
        # a share of an expert layer (ops/moe.py): assignments of real
        # tokens to experts this engine does not hold, left out
        "moe_routed_absent_total",
        # linear-attention layers and their recurrent state, from every
        # step's plan on the host (engine._account_linattn):
        "linattn_tokens_total",        # (token, linear layer) updates
        "linattn_chunk_tokens_total",  # of those, the rows of an
        #                                _engine_step (chunk rows and the
        #                                one-token rows beside them); the
        #                                rest rode a decode window
        "linattn_inplace_updates_total",   # of all of them, those made
        #                                where the state rests (ops/
        #                                linear_attention.kda_step_slots):
        #                                a window's, and a mixed step's
        #                                one-token rows
        "linattn_flat_steps_total",    # the _engine_steps whose linear
        #                                layers worked over a compact
        #                                step's FLAT token rows (models/
        #                                llama.kda_mix_rows where the real
        #                                tokens fit the flat width); a split
        #                                step that is not counted read the
        #                                grid's rows
        "linattn_state_bytes_total",   # state bytes read + written: live
        #                                rows x linear layers x slot bytes x 2
        "linattn_steps_total",         # device steps the above were summed
        #                                over: 1 an _engine_step, a window
        #                                its steps
        "linattn_window_state_bytes_total",  # the same two over decode
        "linattn_window_steps_total",        # windows alone
        "state_slots_used",       # gauge: recurrent-state slots held
        "state_bytes_per_slot",   # gauge: bytes of one slot, all layers
        # one record a step() call (close_call), folded by kind. A call's
        # period = the `between` before it + its time inside step(); a
        # pipelined window's is the call that commits it, a call that
        # only dispatched one is of the kind `decode` too
        "period_mixed_seconds",   # calls that committed a mixed step
        "period_decode_seconds",  # calls that committed or only
        #                           dispatched a decode window
        "period_other_seconds",   # prefill, spec, stream, and calls
        #                           that found nothing to run
        "period_seconds",         # all: the busy wall time
        "window_steps_total",     # device steps of committed windows
        #                           (`steps_decode` counts the windows)
        # `host_between_seconds` in four parts, by the marks of the
        # worker's loop (llm/worker.py _step_loop); they sum to it
        "host_resume_seconds",    # step() exit -> the coroutine running
        #                           again: the future's way back through
        #                           the event loop
        "host_emit_seconds",      # -> the end of the loop body: frame
        #                           fan-out, metrics snapshot, event plane
        "host_apply_pending_seconds",  # -> after _apply_pending
        "host_submit_seconds",    # -> step() entry: the executor's pick-up
        "host_exposed_between_seconds",  # of `between`, the part with no
        #                           program in flight (PhaseTimer.add)
        # `host_exposed_seconds` by the call in whose period it accrued
        # (close_call): two particular exposures, each by itself; what
        # is left of the sum is the steady loop's
        "host_exposed_drain_seconds",     # the calls after a commit that
        #                           ended a row under a window in flight
        #                           (the commits `pipeline_fallbacks`
        #                           counts), up to and with the first that
        #                           launches a program again
        "host_exposed_handover_seconds",  # a call that LAUNCHES a step of
        #                           another kind than the launch before it
        #                           (mixed against window: the hand-overs
        #                           `handovers` counts at their commit, a
        #                           call later), unless it is a drain's
        # calls that committed a step, first dispatched no program, and
        # whose period passed STALL_PERIOD_S: no capture is long enough
        # to catch one, a run can now say it had one
        "period_stalls_total",
        "period_stall_seconds",       # their periods
        "period_stall_wait_seconds",  # of those, inside `wait`: the
        #                           device's or the runtime's side, not
        #                           the host's phases
        # what made each gap between two commits to one stream
        # (_account_gaps): the gap before the FIRST token a commit gives
        # a stream goes whole to one class, by the programs committed
        # since the stream's last commit
        "gap_mixed_seconds",      # one program since: a mixed step
        "gap_mixed_total",
        "gap_window_seconds",     # one program since: a decode window of
        "gap_window_total",       # any rung (or a verify / streamed step)
        "gap_multi_seconds",      # two or more: the stream sat out a
        "gap_multi_total",        # program, or its commit was deferred
        "gap_total",              # the three classes' counts
        "gap_burst_total",        # further tokens of one commit: they
        #                           reach the client ~0 ms apart, no seconds
    )

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        for name in self.FIELDS:
            setattr(self, name, 0)

    def snapshot(self) -> Dict[str, float]:
        return {name: getattr(self, name) for name in self.FIELDS}


LEDGER_STATS = LedgerStats()

_JAX_LISTENERS = False


def install_jax_listeners() -> None:
    """Fold jax's own compile events into LEDGER_STATS, once per process.
    `backend_compile_duration` wraps compile_or_get_cached, so it fires
    for a program XLA builds and for one it loads from the persistent
    cache alike; `cache_hits` tells the two apart. Unlike `recompiles`
    (the engine's set of keys it believes identify a program) these count
    every jit in the process, with the seconds each took."""
    global _JAX_LISTENERS
    if _JAX_LISTENERS:
        return
    _JAX_LISTENERS = True
    from jax import monitoring

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            LEDGER_STATS.jax_compiles += 1
            LEDGER_STATS.jax_compile_seconds += duration

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            LEDGER_STATS.jax_cache_hits += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


# a call's period past this is a stall (close_call): the longest sound
# period in the ledger is a window of 8 x 27 ms
STALL_PERIOD_S = 0.5

_KINDS = ("prefill", "decode", "mixed", "spec", "stream")

# one ring record: the committed step's sample (record_step), then the
# call's own fields (close_call; _NO_CALL until the call is closed, and
# for a sample recorded outside a step() call)
_KEYS = ("ts", "dt", "kind", "rows", "rows_live", "tokens_useful",
         "tokens_padded", "kv_used", "kv_total", "host_used",
         "host_total", "disk_used", "disk_total", "waiting",
         "recompiles", "stream_hit", "stream_late", "stream_spilled",
         "stream_stalls", "tok_s", "dev_steps", "streams",
         "tokens", "bucket", "t_entry", "t_exit", "between", "resume",
         "emit", "apply_pending", "submit", "phases", "stall")
_DEV_STEPS = _KEYS.index("dev_steps")
_CALL = _KEYS.index("bucket")
_NO_CALL = (None, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 0.0, None, False)


class StepLedger:
    """The bounded per-step sample ring + gauge fold for one engine.

    `stats` defaults to the process-global LEDGER_STATS (what /metrics
    renders); pass a private LedgerStats for isolation in tests. The
    EWMA smoothing (`tok_s`) uses alpha=0.2 over per-step instantaneous
    rates."""

    EWMA_ALPHA = 0.2

    def __init__(self, capacity: Optional[int] = None,
                 enabled: Optional[bool] = None,
                 stats: Optional[LedgerStats] = None):
        if enabled is None:
            enabled = os.environ.get("DYN_LEDGER", "1") not in ("", "0")
        if capacity is None:
            capacity = int(os.environ.get("DYN_LEDGER_CAP", "4096"))
        self.enabled = bool(enabled)
        self.capacity = max(1, int(capacity))
        self.stats = stats if stats is not None else LEDGER_STATS
        self._recs: List[list] = []
        self._pos = 0
        self.dropped = 0
        # the sample record_step appended in the step() call in progress,
        # until close_call gives it the call's clock
        self._open: Optional[list] = None
        # request_id -> (perf_counter of its last commit, programs
        # committed by then): what made each gap (_account_gaps)
        self._last_commit: Dict[str, tuple] = {}
        self._programs = 0
        self._last_ts = 0.0
        self._tok_s = 0.0
        # the engine's exposed seconds (PhaseTimer.exposed) as the last
        # close_call found them, and whether a call it closed ended a
        # row under a window in flight and none has launched a program
        # since: the next call's period is a drain's
        self._exposed_seen = 0.0
        self._draining = False
        # the kind of the last program launched, "mixed" or "window"
        self._launched: Optional[str] = None
        # per-INSTANCE cumulative counters (metrics() reads these; the
        # shared `stats` fold is process-cumulative across engines)
        self.steps = 0
        self.recompiles_total = 0
        self.useful_total = 0
        self.padded_total = 0

    def configure(self, enabled: Optional[bool] = None,
                  capacity: Optional[int] = None) -> "StepLedger":
        if enabled is not None:
            self.enabled = enabled
        if capacity is not None:
            self.capacity = max(1, int(capacity))
            self._recs, self._pos, self._open = [], 0, None
        return self

    # -- recording (deferred-recorder discipline: host ints only) -------------

    def record_step(self, kind: str, rows: int, rows_live: int,
                    useful: int, padded: int,
                    kv_used: int, kv_total: int,
                    host_used: int, host_total: int,
                    disk_used: int, disk_total: int,
                    waiting: int, recompiles: int,
                    stream_hit: int = 0, stream_late: int = 0,
                    stream_spilled: int = 0, stream_stalls: int = 0,
                    dense: Optional[int] = None, dev_steps: int = 1,
                    events=(), attn_rows: bool = False) -> None:
        """Record one committed device step. Every argument is
        already-known host state — the disabled path is this one branch.
        `dense`: the token rows the step's token-wise layers ran over
        where that is less than `padded` (a compact step); `attn_rows`:
        such a step's attention ran the row form.
        The stream_* kwargs are this step's window-pool deltas (0 on
        non-streamed kinds); they attribute the prefetch leg per step
        in the drained JSONL. `dev_steps`: the device steps the
        committed program ran (a window's rung). `events`: the commit's
        StepOutputs, from which the gaps between a stream's commits are
        classed (_account_gaps)."""
        if not self.enabled:
            return
        streams, tokens = self._account_gaps(kind, events)
        now = time.monotonic()
        dt = now - self._last_ts if self._last_ts else 0.0
        self._last_ts = now
        if 0.0 < dt < 60.0:
            inst = useful / dt
            self._tok_s += self.EWMA_ALPHA * (inst - self._tok_s)
        self._open = self._append(
            [now, dt, kind, rows, rows_live, useful, padded,
             kv_used, kv_total, host_used, host_total,
             disk_used, disk_total, waiting, recompiles,
             stream_hit, stream_late, stream_spilled, stream_stalls,
             self._tok_s, dev_steps, streams, tokens, *_NO_CALL])
        self.steps += 1
        self.recompiles_total += recompiles
        self.useful_total += useful
        self.padded_total += padded
        s = self.stats
        s.steps_total += 1
        setattr(s, "steps_" + kind, getattr(s, "steps_" + kind) + 1)
        if kind == "decode":
            s.window_steps_total += dev_steps
        s.recompiles += recompiles
        s.tokens_useful += useful
        s.tokens_padded += padded
        if dense is not None and dense < padded:
            s.tokens_dense += dense
            s.compact_steps_total += 1
            s.attn_split_steps_total += int(attn_rows)
        else:
            s.tokens_dense += padded
        k = kind if kind in ("prefill", "decode", "mixed") else "decode"
        setattr(s, "useful_tokens_" + k,
                getattr(s, "useful_tokens_" + k) + useful)
        setattr(s, "padded_tokens_" + k,
                getattr(s, "padded_tokens_" + k) + padded)
        s.kv_pages_used = kv_used
        s.kv_pages_total = kv_total
        s.host_pages_used = host_used
        s.host_pages_total = host_total
        s.disk_pages_used = disk_used
        s.disk_pages_total = disk_total
        s.batch_rows_live = rows_live
        s.batch_rows_total = rows
        s.queue_depth = waiting
        s.stream_prefetch_hit += stream_hit
        s.stream_prefetch_late += stream_late
        s.stream_pages_spilled += stream_spilled
        s.stream_stall_steps += stream_stalls
        s.tok_s = self._tok_s

    def _append(self, rec: list) -> list:
        if len(self._recs) < self.capacity:
            self._recs.append(rec)
        else:
            self._recs[self._pos] = rec
            self._pos = (self._pos + 1) % self.capacity
            self.dropped += 1
            self.stats.samples_dropped = self.dropped
        return rec

    def _account_gaps(self, kind: str, events) -> tuple:
        """Class the gap before the first token this commit gives each
        stream, by the programs committed since the stream's last commit
        (this one included): one and a mixed step `mixed`, one and
        anything else (a decode window of any rung, a verify or streamed
        step) `window`, two or more `multi`. The gap's seconds go whole
        to that class, so a stream's classes sum to its last commit less
        its first; a request's first token is no gap; further tokens of
        one commit are `burst`, counted without seconds. Returns
        (streams that got a token, tokens committed)."""
        now = time.perf_counter()
        self._programs += 1
        n_prog = self._programs
        last = self._last_commit
        counts: Dict[str, int] = {}
        done = []
        for ev in events:
            if ev.token is not None:
                counts[ev.request_id] = counts.get(ev.request_id, 0) + 1
            if ev.finished:
                done.append(ev.request_id)
        s = self.stats
        tokens = 0
        for rid, n in counts.items():
            tokens += n
            prev = last.get(rid)
            if prev is not None:
                gap = now - prev[0]
                if n_prog - prev[1] > 1:
                    s.gap_multi_seconds += gap
                    s.gap_multi_total += 1
                elif kind == "mixed":
                    s.gap_mixed_seconds += gap
                    s.gap_mixed_total += 1
                else:
                    s.gap_window_seconds += gap
                    s.gap_window_total += 1
                s.gap_total += 1
            last[rid] = (now, n_prog)
        s.gap_burst_total += tokens - len(counts)
        for rid in done:
            last.pop(rid, None)
        return len(counts), tokens

    def forget(self, request_id: str) -> None:
        """An aborted request commits no more: drop its last commit."""
        self._last_commit.pop(request_id, None)

    def split_between(self, parts: tuple) -> None:
        """The four parts of the `between` a step() call just charged
        (PhaseTimer.add, at the call's entry: the parts are summed at
        the same instant, so a scrape finds them equal to it)."""
        if not self.enabled:
            return
        s = self.stats
        s.host_resume_seconds += parts[0]
        s.host_emit_seconds += parts[1]
        s.host_apply_pending_seconds += parts[2]
        s.host_submit_seconds += parts[3]

    def close_call(self, dispatched: str, bucket, t_entry: float,
                   t_exit: float, between: float, parts: tuple,
                   phases: Dict[str, list], exposed: float = 0.0,
                   launched: Optional[str] = None,
                   ended_row: bool = False,
                   first_dispatch: bool = False) -> None:
        """The end of one step() call: fold its period into the series of
        its kind and give its record the call's clock. The kind is the
        committed step's; `dispatched` ("decode") names a call that
        committed nothing and primed or chained a window; a call that
        did neither found nothing to run, adds to `period_other_seconds`
        and leaves no record. `parts`: the four parts of `between`, for
        the record (split_between has summed them). `exposed`: the
        engine's cumulative exposed seconds (PhaseTimer.exposed); what
        they grew by since the last call closed accrued in THIS call's
        period, and goes to `host_exposed_drain_seconds` while the loop
        drains: from the call after one whose commit ended a row under a
        window in flight (`ended_row`, as a call says of itself) up to
        and with the first call that `launched` a program again (the
        drained window's own commit is exposed in one call, the plan,
        upload and dispatch behind it in the next); else to
        `host_exposed_handover_seconds` where the step this call
        `launched` (its kind; None: it launched none) is of another kind
        than the launch before it, mixed against window: a hand-over made
        ahead exposes nothing, one made late its plan, upload and
        dispatch. (By the kind of the step a call COMMITS, as `handovers`
        counts, the sum read 0.0 s over 119 hand-overs on the chip: on
        the two-deep loop a step commits a call after its launch, when
        nothing is exposed any more.) `first_dispatch`: the call launched
        a program for the first time (a compile is no stall)."""
        if not self.enabled:
            return
        rec, self._open = self._open, None
        kind = rec[2] if rec is not None else dispatched
        s = self.stats
        period = between + t_exit - t_entry
        grew = max(0.0, exposed - self._exposed_seen)
        self._exposed_seen = exposed
        handover = False
        if launched:
            now = launched if launched in ("mixed", "window") else None
            handover = bool(now and self._launched
                            and now != self._launched)
            self._launched = now
        if self._draining:
            s.host_exposed_drain_seconds += grew
        elif handover:
            s.host_exposed_handover_seconds += grew
        self._draining = ended_row or (self._draining and not launched)
        stall = rec is not None and not first_dispatch \
            and period > STALL_PERIOD_S
        if stall:
            s.period_stalls_total += 1
            s.period_stall_seconds += period
            s.period_stall_wait_seconds += phases.get("wait", (0, 0.0))[1]
        s.period_seconds += period
        if kind == "mixed":
            s.period_mixed_seconds += period
        elif kind == "decode":
            s.period_decode_seconds += period
        else:
            s.period_other_seconds += period
        if rec is None:
            if not kind:
                return
            rec = self._append(
                [time.monotonic(), 0.0, kind, *(0,) * 16, self._tok_s,
                 0, 0, 0, *_NO_CALL])
        rec[_CALL:] = (bucket, t_entry, t_exit, between, *parts, phases,
                       stall)

    # -- derived figures (engine metrics()) -----------------------------------

    @property
    def tok_s(self) -> float:
        return self._tok_s

    def pad_fraction(self) -> float:
        """Cumulative padded-but-useless fraction of device step tokens
        for THIS engine (bucket-ladder waste across every step kind)."""
        if self.padded_total <= 0:
            return 0.0
        return 1.0 - self.useful_total / self.padded_total

    # -- export (off the serving path) ----------------------------------------

    def __len__(self) -> int:
        """Samples `drain()` would return: the calls that committed."""
        return sum(1 for rec in self._recs if rec[_DEV_STEPS])

    def calls(self, clear: bool = False) -> List[Dict[str, Any]]:
        """The ring, oldest first, as JSONL-ready dicts: every call's
        record, the ones that committed nothing included. A sample that
        no call has closed yet (the call in progress on the engine's
        thread, a sample recorded outside step()) has `phases` None."""
        recs = self._recs[self._pos:] + self._recs[:self._pos]
        if clear:
            self._recs, self._pos, self._open = [], 0, None
        out = []
        for rec in recs:
            d = dict(zip(_KEYS, rec))
            d["ts"] = round(d["ts"], 6)
            d["dt"] = round(d["dt"], 6)
            d["tok_s"] = round(d["tok_s"], 3)
            out.append(d)
        return out

    def drain(self, clear: bool = True) -> List[Dict[str, Any]]:
        """Collect the per-step samples, oldest first, as JSONL-ready
        dicts: the records of the calls that committed a step."""
        return [d for d in self.calls(clear=clear) if d["dev_steps"]]

    def write_jsonl(self, path: str, clear: bool = True) -> int:
        """Append the drained samples to an evidence JSONL under the
        tools/artifacts.py policy; returns the record count."""
        from tools.artifacts import append_jsonl
        recs = self.drain(clear=clear)
        for rec in recs:
            append_jsonl(path, rec)
        return len(recs)

    def summary(self) -> Dict[str, Any]:
        """Aggregate view over the resident ring (fleet_storm evidence)."""
        recs = self.drain(clear=False)
        by_kind: Dict[str, int] = {}
        for r in recs:
            by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + 1
        useful = sum(r["tokens_useful"] for r in recs)
        padded = sum(r["tokens_padded"] for r in recs)
        return {
            "samples": len(recs),
            "dropped": self.dropped,
            "steps_by_kind": by_kind,
            "tokens_useful": useful,
            "tokens_padded": padded,
            "pad_waste_frac": round(1.0 - useful / padded, 4)
            if padded else 0.0,
            "recompiles": sum(r["recompiles"] for r in recs),
            "kv_used_last": recs[-1]["kv_used"] if recs else 0,
            "tok_s_last": recs[-1]["tok_s"] if recs else 0.0,
        }
