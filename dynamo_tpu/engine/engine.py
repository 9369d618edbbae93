"""NativeEngine: the JAX/XLA serving engine.

This replaces the reference's GPU engine side-cars (vLLM/SGLang subprocesses
over ZMQ, TRT-LLM over C++ FFI — reference: lib/llm/src/engines/, SURVEY.md
§2.8) with an in-process JAX engine: the model runs under jit on the local
mesh, the KV cache is donated across steps so it never leaves HBM, and the
scheduler (engine/scheduler.py) feeds bucketed static-shape steps so XLA
compiles a small fixed program set.

Step fusion: forward + last-token gather + sampling are one jitted program, so
only the sampled token ids ([B] int32) cross the device->host boundary each
step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import logging
import time
from typing import Dict, List, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dynamo_tpu.engine.config import (
    EngineConfig, ModelConfig, kv_heads_per_row, kv_row_lanes,
    refuse_unserved,
)
from dynamo_tpu.engine.kv_cache import SequenceState
from dynamo_tpu.engine.offload import CopyStream, HostKvPool
from dynamo_tpu.engine.sampler import (
    RepPenaltyCache, SamplingArrayCache,
    sample_logits as _sample_logits, seen_token_mask,
)
from dynamo_tpu.engine.scheduler import (
    PENDING_TOKEN,
    DecodePlan, EngineRequest, MixedPlan, PrefillPlan, SamplingParams,
    Scheduler, StreamPlan, next_bucket, pow2_buckets,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.llama import AttnMetadata
from dynamo_tpu.observability.serving import SERVING
from dynamo_tpu.parallel.mesh import single_device_mesh
from dynamo_tpu.runtime.tracing import TRACER


@dataclasses.dataclass
class StepOutput:
    """One emitted event for one request after an engine step."""

    request_id: str
    token: Optional[int]           # None when finished without a new token
    finished: bool = False
    finish_reason: Optional[str] = None   # "stop" | "length" | "cancelled"
    # populated when the request asked for logprobs (SamplingParams.logprobs
    # is not None): logprob of `token`, and the top-K alternatives
    logprob: Optional[float] = None
    top_logprobs: Optional[List[tuple]] = None  # [(token_id, logprob), ...]


class NativeEngine:
    """Continuous-batching JAX engine for one model on one mesh."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        engine_cfg: EngineConfig,
        mesh: Optional[Mesh] = None,
        params=None,
        eos_token_ids: Optional[Set[int]] = None,
        seed: int = 0,
    ):
        self.mesh = mesh if mesh is not None else single_device_mesh()
        # KV-cache quantization knob: the EngineConfig surface mirrors the
        # weight `quant` knob and overrides ModelConfig.kv_quant (the
        # model code reads cfg.kv_quant at trace time; ops/kv_quant.py)
        from dynamo_tpu.ops.kv_quant import validate_mode as _kvq_validate
        if engine_cfg.kv_quant:
            _kvq_validate(engine_cfg.kv_quant)
            model_cfg = dataclasses.replace(model_cfg,
                                            kv_quant=engine_cfg.kv_quant)
        _kvq_validate(model_cfg.kv_quant)
        self.kv_quant = model_cfg.kv_quant
        # pipeline parallelism (mesh axis "pp", models/pp.py): layer-sharded
        # params/cache, microbatched GPipe schedule. The pp path uses the
        # gather attention everywhere (the Pallas kernel doesn't run under
        # the pp shard_map). Greedy and sampled decode run multi-token
        # windows via the microbatch round-robin (pp_decode_window,
        # VERDICT r3 weak #7 + r4 #6); logprob/penalty plans fall back to
        # per-token dispatch.
        self.pp = self.mesh.shape.get("pp", 1)
        refuse_unserved(model_cfg, engine_cfg, self.mesh)
        if model_cfg.experts_held and model_cfg.moe_impl == "dispatch" \
                and not model_cfg.moe_dropless:
            raise ValueError(
                f"experts_held={model_cfg.experts_held}: a share of an "
                f"expert layer is computed by the dropless dispatch alone "
                f"(num_experts > 8); the capacity form is not told which "
                f"experts it holds")
        if self.mesh.size > 1 and model_cfg.moe_dropless \
                and model_cfg.moe_impl == "dispatch":
            # the dropless dispatch (ops/moe.py) is one device's; what a
            # mesh gets is the capacity form, sized for 8 experts: at 64
            # experts of 8 a token it drops assignments in every chunk
            # and computes every expert for every decode row
            raise ValueError(
                f"num_experts={model_cfg.num_experts}: a many-expert "
                f"model is served on one device only (the multi-device "
                f"MoE dispatch is capacity-based and would drop "
                f"assignments); run it without --tp/--ep/--dp")
        if self.pp > 1:
            # what the MODEL cannot be on a pp mesh (experts, QK-norm under
            # tp) is refused in models/pp.refuse_unserved, reached below
            # through pp_param_shardings
            if engine_cfg.sp > 1:
                raise ValueError("pp and sp (ring attention) do not compose")
            if model_cfg.decode_kernel == "on":
                raise ValueError(
                    "decode_kernel='on' cannot be served on a pp mesh (the "
                    "Pallas kernel does not run under the pp shard_map); "
                    "use decode_kernel='auto'")
            model_cfg = dataclasses.replace(model_cfg, decode_kernel="off")
            if engine_cfg.max_slots % self.pp:
                # decode slot-groups are the pipeline microbatches, so the
                # windowed pp decode needs slots % pp == 0. Round up
                # instead of raising (ADVICE r4): per-token-path workloads
                # never hit the constraint, and for windowed ones a few
                # extra slots beat a config error
                rounded = -(-engine_cfg.max_slots // self.pp) * self.pp
                logging.getLogger(__name__).info(
                    "pp=%d: rounding max_slots %d up to %d (decode "
                    "slot-groups are the pipeline microbatches)",
                    self.pp, engine_cfg.max_slots, rounded)
                engine_cfg = dataclasses.replace(
                    engine_cfg, max_slots=rounded)
        # the compiled kernel has hard constraints the XLA gather path
        # doesn't: a tile-aligned DMA geometry (ops/paged_attention.py
        # kernel_supported) and, under shard_map, tp dividing the head
        # counts. decode_kernel="on" is an explicit request: refuse it
        # here, by name, rather than serve another path under its label
        # or die at the first decode compile. (_decode_kernel_mode itself
        # raises for models whose soft-caps / sliding windows / query
        # scaling the kernel has no hooks for.)
        tp = self.mesh.shape.get("tp", 1)
        if llama._decode_kernel_mode(model_cfg) == "tpu":
            from dynamo_tpu.ops.paged_attention import kernel_supported
            h, hkv = model_cfg.num_heads, model_cfg.num_kv_heads
            if not kernel_supported(model_cfg.head_dim,
                                    engine_cfg.page_size):
                raise ValueError(
                    f"decode_kernel='on': no tile-aligned DMA path for "
                    f"head_dim={model_cfg.head_dim}, page_size="
                    f"{engine_cfg.page_size}; use decode_kernel='auto'")
            if self.mesh.size > 1 and (h % tp or hkv % tp):
                raise ValueError(
                    f"decode_kernel='on': num_heads={h} / num_kv_heads="
                    f"{hkv} not divisible by tp={tp}; use "
                    f"decode_kernel='auto'")
        # what a row of the device pool holds (KV heads that share it, the
        # lanes it is stored in): resolved HERE, once, from shapes and the
        # mesh (engine/config.kv_heads_per_row, kv_row_lanes); every
        # program closes over it through `model_cfg`. Streamed decode
        # (engine/streaming.py) attends over pages staged from the host
        # tier beside the resident ones, in the form they travel in: its
        # pool keeps the model's own rows
        streamed = bool(engine_cfg.stream_pages)
        model_cfg = dataclasses.replace(
            model_cfg,
            kv_row_heads=1 if streamed else kv_heads_per_row(model_cfg, tp),
            kv_row_lanes=0 if streamed else kv_row_lanes(model_cfg, tp))
        self.model_cfg = model_cfg
        self.cfg = engine_cfg
        self.eos_token_ids = set(eos_token_ids or ())
        # host KV tier (reference: multi-tier KV block manager, SURVEY.md
        # §2.5): evicted HBM pages spill to a host slab and come back on
        # prefix hits instead of being recomputed
        self.host_pool = None
        if engine_cfg.host_pages > 0:
            page_shape = (model_cfg.num_layers, model_cfg.num_kv_heads,
                          engine_cfg.page_size, model_cfg.head_dim)
            # tier slabs store the DEVICE representation verbatim: int8
            # pages + f32 scale rows under kv_quant (spill/promote never
            # dequantize; checksums cover the quantized bytes), a head a
            # row whatever the pool's rows hold (extract_pages)
            np_dtype = (np.dtype(np.int8) if self.kv_quant
                        else jnp.empty((), model_cfg.dtype).dtype)
            self.host_pool = HostKvPool(engine_cfg.host_pages, page_shape,
                                        np_dtype,
                                        disk_pages=engine_cfg.disk_pages,
                                        disk_dir=engine_cfg.disk_dir,
                                        scale_shape=(page_shape[:-1]
                                                     if self.kv_quant
                                                     else None))
        # a model with linear-attention layers, or with a state-space
        # mixer beside its attention, keeps a recurrent state a sequence:
        # one slot a decode slot, and one a row of a prefill batch (a
        # sequence has pages from its first chunk and a decode slot only
        # at its last)
        # whether any layer holds pages (none of a power-retention model's
        # does: no pool leaf, no base to gather, no `attn_*` series)
        self._paged = model_cfg.num_cache_layers > 0
        self._state_slots = 0
        if model_cfg.has_state:
            self._state_slots = engine_cfg.max_slots \
                + max(1, engine_cfg.max_prefill_batch)
        # a model whose sliding layers keep a page pool of their own:
        # sized so that it never refuses what the full pool admits, the
        # most a sequence can hold there (the table of the widest chunk)
        # for every decode slot and every row of a prefill batch
        self._window_pages = 0
        window = None
        if model_cfg.window_pool:
            from dynamo_tpu.engine.scheduler import window_table_pages
            rows = engine_cfg.max_slots + max(1, engine_cfg.max_prefill_batch)
            self._window_pages = rows * window_table_pages(
                engine_cfg, model_cfg.sliding_window,
                engine_cfg.max_prefill_chunk)
            window = (model_cfg.sliding_window, self._window_pages)
        self.scheduler = Scheduler(engine_cfg, host_pool=self.host_pool,
                                   state_slots=self._state_slots,
                                   window=window)
        self._pending_offloads: list = []
        self._copy_stream = None
        # cluster-wide shared KV pool (engine/kv_pool.py): attach_kv_pool
        # wires the content-addressed tier + the publish stream
        self.kv_pool = None
        self.kv_pool_source = ""
        self._pool_stream = None
        if self.host_pool is not None:
            self.scheduler.allocator.on_evict = self._offload_page
            self._copy_stream = CopyStream(self.host_pool)
            self.scheduler.settle_hashes = self._copy_stream.settle
        self.step_count = 0
        # decode-window occupancy accounting (VERDICT r3 weak #3)
        self.window_slot_steps = 0    # device (step, live-slot) pairs run
        self.window_wasted_steps = 0  # of those, after the slot finished
        # speculative-decoding accounting (engine/spec.py): acceptance
        # rate = accepted / proposed sizes the workload's lookup-friendliness
        self.spec_steps = 0           # verify forwards dispatched
        self.spec_proposed_tokens = 0
        self.spec_accepted_tokens = 0
        self._spec_acc_ema = 1.0      # optimistic until measured
        self._spec_gate_skips = 0     # rejections since the last probe
        self._finished_cb = None
        self._last_logprobs = None  # (lp, top_ids, top_lps) of last step
        self._dec_state = None      # device-resident decode window state
        # overlapped decode pipeline (docs/PERF.md): the in-flight window
        # record — dispatched, outputs transferring to host asynchronously,
        # commit deferred to the next step() so host bookkeeping for window
        # N runs concurrently with device execution of window N+1
        self._pipeline = None
        # the mixed chain (_chain_step): the mixed step in flight, which
        # the next step() commits behind the dispatch of its successor;
        # `_ahead_failed`: the last plan made ahead came to nothing
        self._flight = None
        self._ahead_failed = False
        # host staging caches: static sampling-param blocks and incremental
        # repetition-penalty history rebuild only when the slot set changes.
        # Mixed steps get their OWN cache pair: a mixed step's row set
        # (decode slots + prefill rows) interleaves with the decode
        # window's slot set, and one shared cache would rebuild on every
        # alternation between the two step kinds
        self._samp_cache = SamplingArrayCache()
        self._rp_cache = RepPenaltyCache()
        self._mixed_samp_cache = SamplingArrayCache()
        self._mixed_rp_cache = RepPenaltyCache()
        # host-loop phase attribution, one vocabulary on every step kind
        # (plan / upload / dispatch / wait / commit; observability/metrics
        # PhaseTimer; the llm_engine_host_* gauges and each call's record
        # in the StepLedger read it)
        from dynamo_tpu.observability.metrics import PhaseTimer
        self.phases = PhaseTimer()
        # perf_counter at the last step()'s return, None while idle:
        # step() charges the gap to `between` (note_idle resets it)
        self._t_step_exit: Optional[float] = None
        # the caller's marks inside that gap (note_between), and the
        # program key and step kind of what the step() call in progress
        # launched (_dispatch_phase)
        self._between_marks: Optional[tuple] = None
        self._call_key: Optional[tuple] = None
        self._call_kind: Optional[str] = None
        # requests that have not sampled a first token yet:
        # request_id -> [t_add, t_first_planned | None, steps, trace]
        # (the engine-side split of first-token time, _mark_planned)
        self._first_token_marks: Dict[str, list] = {}
        # per-step resource ledger (observability/ledger.py): bounded
        # ring of step samples recorded at the commit sites below — the
        # deferred-recorder discipline (host ints only, never a jax
        # array), branch-only when DYN_LEDGER=0; drains as JSONL, folds
        # into the llm_engine_* gauges
        from dynamo_tpu.observability.ledger import (
            StepLedger, install_jax_listeners,
        )
        install_jax_listeners()
        self.ledger = StepLedger()
        self.ledger.stats.kv_bytes_per_token = model_cfg.kv_bytes_per_token()
        self.ledger.stats.kv_heads_per_row = model_cfg.kv_row_heads
        # (0 lanes: no layer holds a page, so no row is stored)
        self.ledger.stats.kv_row_lanes = model_cfg.kv_cache_leaves().get(
            "k", (0, 0))[1]
        self.ledger.stats.kv_bytes_per_token_full = \
            model_cfg.kv_bytes_per_token()
        self.ledger.stats.kv_bytes_per_token_window = \
            model_cfg.window_kv_bytes_per_token()
        self.ledger.stats.kv_window_pages_total = self._window_pages
        self.ledger.stats.state_bytes_per_slot = \
            model_cfg.state_bytes_per_slot()
        # (program, bucket) keys already dispatched: a key's first
        # dispatch is an XLA compile that stalls the serving loop —
        # counted as a recompile event on the ledger sample that commits
        # after it (observability: steady-state serving should hold this
        # flat once the bucket ladder is warm)
        self._seen_programs: set = set()
        self._pending_recompiles = 0
        # program launches so far: the `seq` of a dispatch's annotation
        self._dispatch_seq = 0
        # key -> [the jitted function, its arguments as shapes, the `seq`
        # of its last launch]: what lowers a dispatched program again, so
        # that a profiler capture can leave the optimised HLO of what it
        # saw beside its trace (`program_texts`)
        self._programs: Dict[tuple, list] = {}
        self.phases.stats = self.ledger.stats
        # the phases double as trace spans under the "engine" scope
        # (runtime/tracing.py defer_phase — the hot-path deferred
        # recorder; branch-only when tracing is disabled) and as
        # `engine.<phase>` annotations in a profiler capture
        self.phases.trace_scope = "engine"
        # pipeline occupancy counters (EngineMetrics / /metrics gauges)
        self.decode_windows = 0       # windows dispatched via the window path
        self.decode_dispatches = 0    # device program launches in decode
        self.decode_kernel_tag = ""   # last window's attention-path tag
        self.decode_host_syncs = 0    # blocking output fetches in decode
        self.decode_plan_uploads = 0  # windows that staged fresh host arrays
        self.host_buffers = 0         # host->device buffers the step path
        #                               staged (_stage_operands)
        self.pipeline_windows = 0     # windows committed via the pipeline
        self.pipeline_overlapped = 0  # commits with a follow-up in flight
        self.pipeline_fallbacks = 0   # commits that changed a row's occupant
        #                               under an in-flight follow-up
        self.window_steps_reconciled = 0  # device steps of the follow-ups
        #                               committed after such a commit, for
        #                               the rows still live
        self.window_steps_discarded = 0   # device steps of windows that
        #                               reached no row (every row had left)
        # mixed prefill+decode steps (docs/PERF.md): fused [Bb, Tb] steps
        # run, and the stall counter — device steps where >= 1 running
        # request emitted nothing because the step carried no decode rows
        # (the interference tax the mixed scheduler removes; stays ~0
        # with mixed on, counts the alternating baseline's prefill tax)
        self.mixed_steps = 0
        self.decode_stall_steps = 0
        self.mixed_steps_chained = 0    # dispatched behind a mixed step
        #                                 still in flight
        self.mixed_steps_replanned = 0  # planned again after the commit
        #                                 before them (the plan made ahead
        #                                 came to nothing)
        # a change of step kind, mixed <-> decode window (_note_kind)
        self.handovers = 0          # committed steps of another kind
        #                             than the committed step before them
        self.handovers_chained = 0  # of those, dispatched before that
        #                             step was fetched
        self._last_kind: Optional[str] = None
        # cumulative MoE capacity-drop counters (dispatch impl only)
        self.moe_dropped_tokens = 0.0
        self.moe_routed_tokens = 0.0
        self._moe_drop_warned = False

        if self.pp > 1:
            from dynamo_tpu.models.pp import pp_param_shardings
            param_specs = pp_param_shardings(
                model_cfg, self.mesh.shape.get("tp", 1))
        else:
            param_specs = llama.param_shardings(model_cfg)
        if model_cfg.quant == "int8":
            from dynamo_tpu.ops.quant import (
                quantize_params, quantize_shardings,
            )
            param_specs = quantize_shardings(param_specs, model_cfg)
        elif model_cfg.quant:
            raise ValueError(f"unknown quant mode {model_cfg.quant!r} "
                             "(supported: int8)")
        shardings = jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        if params is None:
            # random init lands SHARDED (out_shardings): no device ever
            # holds the whole tree, so llama3-8b --tp 4 asks each 16 GB
            # chip for its quarter only. jax_threefry_partitionable is on
            # in the installed JAX, so the drawn values do not depend on
            # the sharding — every mesh-vs-oracle parity test compares
            # engines seeded identically and needs init to be
            # mesh-invariant.
            if model_cfg.quant == "int8":
                def init_fn(key):
                    return quantize_params(
                        llama.init_params(key, model_cfg), model_cfg)
            else:
                init_fn = functools.partial(llama.init_params,
                                            cfg=model_cfg)
            params = jax.jit(init_fn, out_shardings=shardings)(
                jax.random.PRNGKey(seed))
        else:
            if model_cfg.quant == "int8":
                from dynamo_tpu.ops.quant import is_quantized
                if not is_quantized(params["layers"].get("wq")):
                    # quantize on HOST so the full-precision tree never
                    # stages through device memory (a 70B bf16 tree
                    # would not fit next to its int8 twin). Loaders may
                    # hand an already-quantized tree (GGUF streams
                    # per-projection quantization during load).
                    params = quantize_params(params, model_cfg, xp=np)
            params = jax.device_put(params, shardings)
        self.params = params
        self._replicated = NamedSharding(self.mesh, P())
        # what an `_engine_step` with no step in flight before it is
        # handed as that step's tokens (`prev_tokens`; none of its rows
        # reads it): one fixed length, the row ladder's cap, and put with
        # the sharding a step's own output has, so that a chained step
        # and a lone one are one program
        self._no_prev = jax.device_put(
            np.full((engine_cfg.max_slots
                     + max(1, engine_cfg.max_prefill_batch),), -1, np.int32),
            self._replicated)
        # the two hand-overs between the chains (_launch_ahead): a window
        # dispatched behind a mixed step takes its rows' pending tokens
        # from that step's tokens, a mixed step behind a window takes the
        # window's last tokens at the length a step's own have. Each out
        # with the sharding a window's own carry has, and compiled here,
        # so that no window of traffic meets either first
        self._carry_fn = jax.jit(_carry_behind,
                                 out_shardings=self._replicated)
        self._prev_fn = jax.jit(
            functools.partial(_tokens_behind, self._no_prev.shape[0]),
            out_shardings=self._replicated)
        self._prev_fn(self._carry_fn(self._no_prev, jax.device_put(
            np.zeros((engine_cfg.max_slots, 4), np.int32),
            self._replicated)))

        init_cache = jax.jit(
            functools.partial(
                llama.init_cache, model_cfg,
                num_pages=engine_cfg.num_pages, page_size=engine_cfg.page_size,
                window_pages=self._window_pages),
            out_shardings=self.cache_shardings)
        self.cache = init_cache()
        if self._state_slots:
            # the recurrent state rides the same dict: every program
            # takes it, donates it and hands it back with the pool
            self.cache.update(jax.jit(functools.partial(
                llama.init_state, model_cfg, self._state_slots))())

        # sequence-parallel prefill (ring attention over the "sp" axis):
        # requires whole-prompt single-chunk prefills and no prefix sharing
        # (the ring path attends only within the chunk)
        sp_mesh = None
        if engine_cfg.sp > 1:
            if self.mesh.shape.get("sp", 1) != engine_cfg.sp:
                raise ValueError(
                    f"engine sp={engine_cfg.sp} but mesh sp axis is "
                    f"{self.mesh.shape.get('sp', 1)}")
            if engine_cfg.max_prefill_chunk < engine_cfg.max_model_len:
                raise ValueError(
                    "sp>1 requires max_prefill_chunk >= max_model_len "
                    "(whole-prompt prefill)")
            if any(b % engine_cfg.sp for b in engine_cfg.prefill_buckets):
                raise ValueError("every prefill bucket must divide by sp")
            if (model_cfg.attn_softcap or model_cfg.sliding_window
                    or model_cfg.query_scale):
                raise ValueError(
                    "sp>1 (ring-attention prefill) does not support "
                    "attention soft-caps / sliding windows / query-scale "
                    "overrides; serve Gemma-2-class models with sp=1")
            sp_mesh = self.mesh
        self._sp_mesh = sp_mesh
        # multi-device meshes hand the mesh to forward() so the Pallas decode
        # kernel runs under shard_map over "tp" instead of falling back to
        # the XLA gather path (a 2-3x HBM-traffic amplification)
        kernel_mesh = self.mesh if self.mesh.size > 1 else None
        eos_tuple = tuple(sorted(self.eos_token_ids))
        # per step kind, a lazy variant grid keyed by (with_rp, with_lp):
        # repetition penalty carries a seen-token mask, logprobs add a
        # full-vocab log_softmax + top_k and extra host transfers — both
        # cost real decode latency, so each is compiled in only for plans
        # that use it (reference engines gate these the same way).
        # The decode window (with_rp=False, with_lp=False) is the hot path:
        # N forward+sample iterations fused into one device program
        # (lax.scan feeds the sampled token to the next step), so host work
        # amortizes over N tokens instead of paying per token.
        pp_mesh = self.mesh if self.pp > 1 else None
        # every program takes its small host operands as ONE packed buffer
        # and a static layout (_packed, _stage_operands): a variant's
        # operand names are fixed here, with the variant
        state_op = ("state_slots",) * bool(self._state_slots)
        wstep_op = ("wtable", "woff", "wwrite_idx") * bool(self._window_pages)
        wwin_op = ("wtable", "woff") * bool(self._window_pages)
        self._step_fns = {
            (rp, lp, mm): jax.jit(
                _named("engine_step", _packed(
                    functools.partial(
                        _engine_step, model_cfg, eos_tuple, sp_mesh,
                        kernel_mesh, rp, lp, mm, pp_mesh),
                    STEP_OPERANDS + state_op + wstep_op
                    + ("rep_penalty",) * rp
                    + ("mm_mask",) * mm,
                    ("hist",) * rp + ("mm_embeds",) * mm, fed=True)),
                static_argnums=(3,), donate_argnums=(1,))
            for rp in (False, True) for lp in (False, True)
            for mm in (False, True)
        }
        # one variant per (rp, lp, greedy, window rung): the 3-rung ladder
        # (full / quarter / 1) bounds the compiled-program set while the
        # scheduler's adaptive choice keeps request tails off the big
        # window (scheduler.window_ladder)
        from dynamo_tpu.engine.scheduler import window_ladder
        self._window_sizes = window_ladder(engine_cfg.decode_steps)
        self._decode_fns = {
            (rp, lp, greedy, nw): jax.jit(
                _named(self._window_name(nw), _packed(
                    functools.partial(
                        _engine_decode_window, model_cfg, eos_tuple,
                        kernel_mesh, nw, engine_cfg.page_size, rp, lp,
                        greedy),
                    WINDOW_OPERANDS + state_op + wwin_op
                    + ("rep_penalty",) * rp,
                    ("hist",) * rp, carried=True)),
                static_argnums=(3,), donate_argnums=(1,))
            for rp in (False, True) for lp in (False, True)
            for greedy in (False, True) for nw in self._window_sizes
        }
        # speculative decoding (engine/spec.py): ONE verify program over a
        # fixed [S, spec_k+1] block — a prefill-shaped forward whose
        # per-position argmax re-derives the greedy choice at every draft
        # position, so acceptance is exact. Greedy-only by design: sampled
        # plans take the decode window (which already amortizes dispatch),
        # so speculation never has to reproduce the stochastic sampler.
        self._verify_fn = None
        self._draft = None
        if engine_cfg.spec_decode:
            if engine_cfg.spec_decode not in ("ngram", "draft"):
                raise ValueError(
                    f"unknown spec_decode mode {engine_cfg.spec_decode!r} "
                    "(supported: 'ngram', 'draft')")
            if engine_cfg.spec_k < 1:
                raise ValueError("spec_decode requires spec_k >= 1")
            if engine_cfg.sp > 1:
                # llama.forward routes ANY Tq>1 forward on an sp mesh to
                # ring attention, which attends only within the chunk —
                # a verify block needs the paged KV prefix, so its logits
                # would be silently wrong
                raise ValueError(
                    "spec_decode does not compose with sp (ring-attention "
                    "prefill); use tp/dp meshes or disable spec_decode")
            # on pp meshes the verify block is just a prefill-shaped
            # pp_forward — the GPipe scan already handles Tq > 1, so the
            # pipelined multi-token forward comes for free
            self._verify_fn = jax.jit(
                _named("engine_verify_step", _packed(
                    functools.partial(
                        _engine_verify_step, model_cfg, eos_tuple, None,
                        kernel_mesh, pp_mesh),
                    VERIFY_OPERANDS)),
                static_argnums=(2,), donate_argnums=(1,))
            if engine_cfg.spec_decode == "draft":
                import os as _os

                from dynamo_tpu.engine.spec import DraftModel
                name = engine_cfg.spec_draft_model
                if not name:
                    raise ValueError(
                        "spec_decode='draft' requires spec_draft_model "
                        "(a registry name or an HF checkpoint dir)")
                dparams = None
                if _os.path.isdir(name):
                    from dynamo_tpu.models.loader import load_model_dir
                    dcfg, dparams = load_model_dir(name)
                else:
                    from dynamo_tpu.engine.config import get_model_config
                    dcfg = get_model_config(name)
                if dcfg.vocab_size != model_cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {dcfg.vocab_size} != target vocab "
                        f"{model_cfg.vocab_size}: the draft's token ids "
                        "feed the target's verify block verbatim")
                self._draft = DraftModel(
                    dcfg, engine_cfg,
                    self.mesh if self.mesh.size > 1 else None,
                    params=dparams, seed=seed)
        # pp decode windows: microbatch round-robin through the pipeline,
        # one variant per (window rung, greedy?) — greedy plans keep the
        # argmax-only program, sampled plans get the full sampler tail
        # (models/pp.py; VERDICT r4 #6)
        self._pp_decode_fns = {}
        if self.pp > 1:
            from dynamo_tpu.models.pp import pp_decode_window
            self._pp_decode_fns = {
                (nw, greedy): jax.jit(
                    _named(self._window_name(nw), _packed(
                        functools.partial(
                            pp_decode_window, self.model_cfg, eos_tuple,
                            self.mesh, nw, engine_cfg.page_size, greedy),
                        PP_WINDOW_OPERANDS, carried=True)),
                    static_argnums=(3,), donate_argnums=(1,))
                for nw in self._window_sizes for greedy in (False, True)
            }
        # disaggregation: whole-page gather/scatter on the cache (the TPU
        # equivalent of the reference's NIXL read/write_blocks, SURVEY.md
        # §2.7); ids are bucketed, out-of-range ids are dropped. Pages
        # leave and enter as [L, Hkv, Nb, ps, hd] whatever a row of THIS
        # pool holds (`kv_row_heads`, which follows the mesh: the two
        # ends of a transfer may differ; `kv_row_pad`, a latent row's
        # zero lanes, which stay behind)
        rows = dict(row_heads=model_cfg.kv_row_heads,
                    pad=model_cfg.kv_row_pad)
        self._extract_fn = jax.jit(functools.partial(_extract_pages, **rows))
        self._inject_fn = jax.jit(functools.partial(_inject_pages, **rows),
                                  donate_argnums=(0,))
        # sharded parallel transfer (disagg/remote_transfer.py): one
        # jitted slice-scatter per shard-slice plan entry — the set is
        # bounded by the transfer layout (parallel/mesh.kv_shard_layout)
        self._inject_shard_fns = {}
        # multimodal: jitted vision tower (models/vision.py); the encoder
        # runs at admission time (the "vision prefill"), its projected
        # patch embeds feed the text prefill via PrefillPlan.mm_embeds
        self._encode_fn = None
        if model_cfg.vision is not None:
            from dynamo_tpu.models import vision as _vision
            self._encode_fn = jax.jit(
                lambda p, px: _vision.encode(p, model_cfg, px))
        # tiered-KV streaming decode (engine/streaming.py): contexts
        # beyond the resident HBM budget attend over cold pages staged
        # from the offload tiers through a double-buffered window pool
        self._streamer = None
        if engine_cfg.stream_pages > 0:
            if engine_cfg.host_pages <= 0:
                raise ValueError(
                    "stream_pages > 0 requires host_pages > 0: cold "
                    "pages live in the host/disk offload tiers")
            if self.pp > 1 or engine_cfg.sp > 1 or self.mesh.size > 1:
                raise ValueError(
                    "tiered-KV streaming runs single-device only for "
                    "now (the per-layer window-pool loop does not "
                    "compose with pp/sp/multi-chip meshes)")
            if engine_cfg.spec_decode:
                raise ValueError(
                    "tiered-KV streaming does not compose with "
                    "spec_decode (the streamed step has no verify "
                    "block); disable one of them")
            if model_cfg.is_moe and model_cfg.moe_impl == "dispatch":
                raise ValueError(
                    "tiered-KV streaming requires moe_impl='dense' on "
                    "MoE models (the streamed per-layer loop uses the "
                    "dense-compute MLP path)")
            if model_cfg.attn_softcap or model_cfg.sliding_window:
                raise ValueError(
                    "tiered-KV streaming supports full attention only "
                    "(no attn_softcap / sliding_window): a sliding "
                    "window never exceeds the resident budget anyway")
            from dynamo_tpu.engine.streaming import StreamingDecoder
            self._streamer = StreamingDecoder(self)
            self.scheduler.stream_enabled = True
            self.scheduler.on_stream_finish = self._streamer.release

    def _window_name(self, nw: int) -> str:
        """Module name of the decode window at ladder rung `nw`. The rung
        is in the name because a window's device time scales with it: a
        metric that divides by `decode_steps` must read the full rung
        alone, never a median over all rungs."""
        return ("engine_decode_window_full" if nw == self._window_sizes[0]
                else f"engine_decode_window_w{nw}")

    def encode_image(self, pixels: np.ndarray) -> np.ndarray:
        """pixels [H, W, 3] or [B, H, W, 3] float in [0,1] ->
        [n_patches, D_text] (or [B, n_patches, D_text]) f32 embeds."""
        if self._encode_fn is None:
            raise ValueError(f"model {self.model_cfg.name!r} has no vision "
                             "encoder configured")
        single = pixels.ndim == 3
        if single:
            pixels = pixels[None]
        out = np.asarray(jax.device_get(
            self._encode_fn(self.params["vision"], jnp.asarray(pixels))))
        return out[0] if single else out

    def device_info(self) -> dict:
        """Where this engine runs, for the launchers' READY lines
        (utils/launch.device_tag): platform and device_kind as JAX reports
        them, the mesh's device ids and non-trivial axes, and — where the
        backend reports it — each mesh device's peak HBM bytes so far.
        After construction that is the weight + cache footprint, which
        shows whether random init staged the model through one device."""
        devices = list(self.mesh.devices.flat)
        info = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "devices": [d.id for d in devices],
            "mesh": {a: n for a, n in self.mesh.shape.items() if n > 1},
        }
        stats = [d.memory_stats() for d in devices]
        if all(s and "peak_bytes_in_use" in s for s in stats):
            info["peak_bytes_in_use"] = [int(s["peak_bytes_in_use"])
                                         for s in stats]
        return info

    @property
    def cache_sharding(self) -> NamedSharding:
        if self.pp > 1:
            from dynamo_tpu.models.pp import pp_cache_sharding
            return NamedSharding(self.mesh, pp_cache_sharding())
        return NamedSharding(self.mesh, llama.cache_sharding(self.model_cfg))

    @property
    def cache_scale_sharding(self) -> NamedSharding:
        """Sharding for KV scale page stacks (kv_quant engines only)."""
        if self.pp > 1:
            from dynamo_tpu.models.pp import pp_cache_scale_sharding
            return NamedSharding(self.mesh, pp_cache_scale_sharding())
        return NamedSharding(self.mesh,
                             llama.cache_scale_sharding(self.model_cfg))

    @property
    def cache_shardings(self):
        """Per-leaf NamedShardings matching the cache dict layout."""
        if self.pp > 1:
            from dynamo_tpu.models.pp import (
                pp_cache_scale_sharding, pp_cache_sharding,
            )
            shd = NamedSharding(self.mesh, pp_cache_sharding())
            out = {"k": shd, "v": shd}
            if self.kv_quant:
                sshd = NamedSharding(self.mesh, pp_cache_scale_sharding())
                out["k_scale"] = sshd
                out["v_scale"] = sshd
            return out
        return {key: NamedSharding(self.mesh, spec) for key, spec in
                llama.cache_shardings(self.model_cfg).items()}

    # -- public API ----------------------------------------------------------

    def _resolve_mm(self, req: EngineRequest) -> EngineRequest:
        """Encode raw image pixels into text-space embeds (the "vision
        prefill"). Salts derive from PIXEL bytes, not embeds, so both sides
        of a disaggregated pair compute identical page hashes regardless of
        vision-tower sharding numerics."""
        if not req.mm_pixels:
            return req
        from dynamo_tpu.engine.kv_cache import content_salt
        spans = list(req.mm_spans or [])
        for off, px in req.mm_pixels:
            px = np.asarray(px, np.float32)
            spans.append((int(off), self.encode_image(px),
                          content_salt(px.tobytes())))
        return dataclasses.replace(req, mm_spans=spans, mm_pixels=None)

    def _validate_prompt(self, req: EngineRequest) -> EngineRequest:
        """Reject out-of-vocab token ids at admission (ValueError -> the
        worker's add path converts it into a per-request error frame).

        An OOV id silently becomes NaN at the embedding gather (jnp.take
        fills out-of-bounds reads), the NaN rides the forward into this
        request's KV pages, and — the insidious part — freed NaN pages
        then poison FUTURE well-formed requests whose masked attention
        reads the recycled rows (0 * NaN = NaN; found by the chaos
        harness as a request completing with another request's
        degenerate argmax-0 tokens). Multimodal span positions are
        exempt: their placeholder ids are rewritten to content-hash
        salts that never feed the embedding table (scheduler._admit)."""
        vocab = self.model_cfg.vocab_size
        ids = np.asarray(req.prompt, dtype=np.int64)
        bad = (ids < 0) | (ids >= vocab)
        for item in (req.mm_spans or ()):
            off, n = int(item[0]), np.asarray(item[1]).shape[0]
            bad[off:off + n] = False
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"request {req.request_id}: token id {req.prompt[i]} at "
                f"position {i} is outside the model vocab [0, {vocab})")
        return req

    def add_request(self, req: EngineRequest) -> None:
        # admission-time copy settling is per-hash and happens inside the
        # prefix walk (scheduler.settle_hashes -> CopyStream.settle): only
        # in-flight copies of pages this request could hit are awaited
        # (VERDICT r3 weak #4); the decode loop never waits at all
        self.scheduler.add_request(
            self._validate_prompt(self._resolve_mm(req)))
        self._first_token_marks[req.request_id] = [
            time.monotonic(), None, 0, req.trace]

    def abort(self, request_id: str) -> bool:
        if self._draft is not None:
            self._draft.forget(request_id)
        self._first_token_marks.pop(request_id, None)
        self.ledger.forget(request_id)
        if self.scheduler.abort(request_id):
            return True
        # a prefill row of the mixed step in flight is in no queue: it
        # ends here, and the step is committed for the other rows
        flight = self._flight
        for i, seq in enumerate(flight["plan"].seqs if flight else ()):
            if seq is not None and seq.request_id == request_id \
                    and not flight["plan"].is_decode[i]:
                self.scheduler.finish(seq)
                flight["dead"].add(i)
                return True
        return False

    def note_idle(self) -> None:
        """The caller's loop is about to sleep for lack of work: the time
        until the next step() is idleness, not host time between steps."""
        self._t_step_exit = None

    def note_between(self, t_resumed: float, t_emitted: float,
                     t_applied: float) -> None:
        """The caller's loop marks the time since the last step()
        returned (`time.perf_counter()`): its coroutine running again,
        the end of its loop body, its staged ops applied. The next
        step() splits `between` at them (`host_resume_seconds`,
        `_emit_`, `_apply_pending_`, `_submit_`: llm/worker.py)."""
        self._between_marks = (t_resumed, t_emitted, t_applied)

    def close(self) -> None:
        """Release background resources (host-tier copy + pool publish
        threads); a step in flight is let go uncommitted."""
        self._flight = None
        if self._copy_stream is not None:
            self._copy_stream.close()
            self._copy_stream = None
        if self._pool_stream is not None:
            self._pool_stream.close()
            self._pool_stream = None

    def has_work(self) -> bool:
        s = self.scheduler
        if s.overlap_gates:
            # early-decode overlap (docs/PERF.md): promote any gated
            # remote sequence whose committed frontier — the MIN over
            # per-stream frontiers on sharded parallel transfers — now
            # covers its transfer list; the watermark check runs HERE,
            # before planning, on the same thread that applies injects
            s.poll_overlap_gates()
        return (self._pipeline is not None or self._flight is not None
                or bool(s.waiting) or bool(s.stream_active)
                or any(x is not None for x in s.running))

    def step(self) -> List[StepOutput]:
        """Run one scheduler step on the device; returns per-request events.

        With pipeline_depth >= 2 the decode loop is two-deep: a step that
        finds an in-flight window dispatches its follow-up FIRST (zero new
        host arrays — the device carry feeds it), then fetches and commits
        the in-flight window's outputs while the follow-up executes on
        device. Events for a pipelined window therefore arrive one step()
        call after its dispatch; greedy and seeded-sampled streams stay
        token-identical to the synchronous loop (docs/PERF.md). Mixed
        steps chain the same way (_chain_step): a call that finds one in
        flight plans and dispatches the next before it fetches it.

        Every step kind passes through the same five host phases (plan,
        upload, dispatch, wait, commit: PhaseTimer), flat and contiguous;
        the time since the previous step() returned is `between`. The
        call's record (its kind and bucket, entry and exit, its own
        phases, `between` and its parts) goes to the StepLedger."""
        t_entry = time.perf_counter()
        between, parts = 0.0, (0.0, 0.0, 0.0, 0.0)
        marks, self._between_marks = self._between_marks, None
        if self._t_step_exit is not None:
            between = t_entry - self._t_step_exit
            self.phases.add("between", between, self._t_step_exit)
            if marks is not None:
                # four parts that sum to `between` by construction
                parts = (marks[0] - self._t_step_exit, marks[1] - marks[0],
                         marks[2] - marks[1], t_entry - marks[2])
                self.ledger.split_between(parts)
        self._call_key = self._call_kind = None
        # the marks the call's record reads a drain and a stall off
        # (ledger.close_call): each a counter the engine keeps
        before = (self.pipeline_fallbacks, len(self._seen_programs))
        try:
            return self._step()
        finally:
            self._t_step_exit = t_exit = time.perf_counter()
            key = self._call_key
            self.ledger.close_call(
                "decode" if key and key[0] in ("window", "ppwindow")
                else "mixed" if self._flight is not None else "",
                self._key_bucket(key), t_entry, t_exit, between,
                parts, self.phases.take_call(), self.phases.exposed,
                launched=self._call_kind,
                ended_row=self.pipeline_fallbacks != before[0],
                first_dispatch=len(self._seen_programs) != before[1])

    def _step(self) -> List[StepOutput]:
        if self._pipeline is not None:
            return self._pipeline_step()
        if self._flight is not None:
            return self._chain_step()
        with self.phases.phase("plan"):
            plan = self.scheduler.schedule()
            self._process_offloads()  # save evicted pages before any overwrite
            self._process_onboards()  # host-tier pages the plan may read
            self._process_pool_injects()  # cluster-tier pages it may read
        if plan is None:
            return []
        self.step_count += 1
        if isinstance(plan, StreamPlan):
            return self._run_stream(plan)
        if isinstance(plan, MixedPlan):
            return self._run_mixed(plan)
        self._ahead_failed = False
        if isinstance(plan, PrefillPlan):
            # decode-stall accounting: a pure prefill step while decode
            # slots are live starves every running stream for this step
            # (exactly what mixed steps remove — bench.py churn phase)
            if any(s is not None for s in self.scheduler.running):
                self.decode_stall_steps += 1
            return self._run_prefill(plan)
        if self._pipeline_ok(plan):
            events = self._prime_pipeline(plan)
            if events is not None:
                return events
        return self._run_decode(plan)

    def generate(self, prompt: List[int], params: SamplingParams,
                 request_id: str = "req") -> List[int]:
        """Synchronous convenience: run one request to completion."""
        self.add_request(EngineRequest(request_id, prompt, params))
        out: List[int] = []
        while True:
            events = self.step()
            done = False
            for ev in events:
                if ev.request_id != request_id:
                    continue
                if ev.token is not None:
                    out.append(ev.token)
                done |= ev.finished
            if done:
                return out
            if not events and not self.has_work():
                return out

    # -- internals -----------------------------------------------------------

    def _sampling_arrays(self, reqs: List[Optional[SequenceState]],
                         mixed: bool = False):
        """(temp, top_k, top_p, seeds, counters, min_toks) per slot. The
        static block is cached per slot set (sampler.SamplingArrayCache):
        per-request params are immutable, so only the counters column is
        rebuilt per step. Mixed steps use their own cache instance so the
        mixed row set and the decode window's slot set don't evict each
        other on every step-kind alternation."""
        cache = self._mixed_samp_cache if mixed else self._samp_cache
        return cache.arrays(reqs, lambda rid: self.scheduler.params[rid])

    def _rep_penalty_arrays(self, reqs: List[Optional[SequenceState]],
                            mixed: bool = False):
        """(hist [S, Hb], rep_penalty [S]) when any request penalizes
        repetition, else None. hist rows are each sequence's seen tokens
        (prompt + generated), padded with vocab_size (dropped on scatter);
        Hb is bucketed so the compiled-program set stays small. Rows are
        updated incrementally across steps (sampler.RepPenaltyCache) —
        only tokens generated since the last call are appended."""
        cache = self._mixed_rp_cache if mixed else self._rp_cache
        return cache.arrays(
            reqs, lambda rid: self.scheduler.params[rid],
            self.model_cfg.vocab_size,
            lambda n: next_bucket(n, pow2_buckets(self.cfg.max_model_len)))

    def _account_moe(self, aux, window: bool = False) -> None:
        """Fold a step's MoE stats (ops/moe.py moe_stats, already on the
        host with the step's outputs) into the `llm_engine_moe_*_total`
        series, and warn once where a capacity dispatch drops (it does so
        silently otherwise — ADVICE r1 medium). A decode `window`'s
        experts hit and layer calls are also kept apart
        (`moe_window_*_total`): its steps hold a row a sequence, so the
        experts they touch, and with them the weight bytes a window step
        reads, are far fewer than a chunk's."""
        from dynamo_tpu.observability.ledger import LEDGER_STATS
        for key, value in aux.items():
            name = f"{key}_total"
            setattr(LEDGER_STATS, name,
                    getattr(LEDGER_STATS, name) + float(value))
        if window:
            LEDGER_STATS.moe_window_experts_hit_total += float(
                aux["moe_experts_hit"])
            LEDGER_STATS.moe_window_layer_calls_total += float(
                aux["moe_layer_calls"])
        self.moe_dropped_tokens += float(aux["moe_dropped"])
        self.moe_routed_tokens += float(aux["moe_routed"])
        rate = self.moe_drop_rate()
        if rate > 0.01 and not self._moe_drop_warned \
                and self.moe_routed_tokens > 1000:
            self._moe_drop_warned = True
            logging.getLogger(__name__).warning(
                "MoE capacity dispatch dropping %.2f%% of (token, expert) "
                "assignments; outputs are degraded (llm_engine_moe_"
                "dropped_total on /metrics)", rate * 100)

    def _wants_logprobs(self, reqs) -> bool:
        return any(seq is not None and
                   self.scheduler.params[seq.request_id].logprobs is not None
                   for seq in reqs)

    def _dispatch_phase(self, key: tuple, kind: str, fn, args: tuple):
        """The `dispatch` phase of one program launch. Recompile detection
        lives here: the first dispatch of a (program, bucket-shape) key is
        an XLA compile or a cache load that stalls the loop, so it is
        annotated `engine.compile` (a trace then names the step that
        stalled), logged once with its seconds, and counted as a
        recompile on the next ledger sample. The annotation carries what
        is launched as stats beside its name: the step's `kind`
        ("prefill" | "mixed" | "window" | "verify"), its bucket (`rows`
        and `chunk` of an `_engine_step` or a verify block, `rows` and
        `rung` of a window), `seq` (the launches so far) and `ahead` (a
        program was still in flight): a capture's reducer gives every
        program the device ran the bucket of its launch
        (observability/profile.py). `fn(*args)` is the launch itself:
        a key's first dispatch keeps `fn` and the arguments' shapes
        (`program_texts`)."""
        self._call_key, self._call_kind = key, kind
        self._dispatch_seq += 1
        stats = {"kind": kind, "seq": self._dispatch_seq,
                 "ahead": int(self.phases.device_busy)}
        if kind == "window":
            stats["rung"] = self._key_bucket(key)
            stats["rows"] = key[-3 if key[0] == "ppwindow" else -4]
        else:
            stats["rows"], stats["chunk"] = self._key_bucket(key)
        if key in self._seen_programs:
            self._programs[key][2] = self._dispatch_seq
            return self.phases.phase("dispatch", stats=stats)
        self._seen_programs.add(key)
        self._programs[key] = [fn, jax.tree.map(_abstract, args),
                               self._dispatch_seq]
        self._pending_recompiles += 1
        return self._first_dispatch(key, stats)

    def program_texts(self, since: int = 0) -> Dict[str, str]:
        """The optimised HLO text of every program launched after the
        `since`-th dispatch (`_dispatch_seq`; 0: all of them), by a name
        that says its module and bucket. Each is lowered again from the
        shapes of its first dispatch: the same jaxpr and lowering as the
        call itself, so jax hands back the executable it already holds
        and nothing is compiled (tests/test_step_tracing.py). For a
        capture's end (llm/worker.py capture_profile), off the engine's
        thread: a trace's ops carry no scope, the HLO's `op_name`s do."""
        out = {}
        for key, (fn, avals, last) in list(self._programs.items()):
            if last <= since:
                continue
            name = fn.__wrapped__.__name__ + "".join(
                f"-{'x'.join(map(str, d)) if isinstance(d, tuple) else d}"
                for d in key[1:] if d is not None and d is not False)
            out[name] = fn.lower(*avals).compile().as_text()
        return out

    # where a program key holds its bucket: the `[Bb, Tb]` grid of an
    # `_engine_step` or a verify block, a decode window's rung
    _BUCKET_AT = {"step": 4, "window": 4, "ppwindow": 2, "verify": 1}

    @classmethod
    def _key_bucket(cls, key: Optional[tuple]):
        """The bucket of a (program, bucket-shape) key, for the call's
        record in the StepLedger; None where the call dispatched none."""
        at = cls._BUCKET_AT.get(key[0]) if key else None
        return key[at] if at is not None else None

    @contextlib.contextmanager
    def _first_dispatch(self, key: tuple, stats: dict):
        t0 = time.perf_counter()
        with self.phases.phase("dispatch", annotation="compile",
                               stats=stats):
            yield
        sch = self.scheduler
        logging.getLogger(__name__).info(
            "first dispatch of %s: %.2fs (%d rows running, %d waiting)",
            key, time.perf_counter() - t0,
            sum(s is not None for s in sch.running), len(sch.waiting))

    def _mark_planned(self, seqs) -> None:
        """A prefill or mixed step is about to run these rows: a request
        planned for the first time has finished queueing
        (`llm_engine_queue_wait_seconds`, span `engine.queue`); every
        step its prompt rides is counted for `engine.prefill`."""
        marks = self._first_token_marks
        if not marks:
            return
        now = None
        for seq in seqs:
            m = marks.get(seq.request_id) if seq is not None else None
            if m is None:
                continue
            m[2] += 1
            if m[1] is None:
                now = now or time.monotonic()
                m[1] = now
                SERVING.engine_queue_wait.observe(value=now - m[0])
                TRACER.record_span("engine.queue", m[3], now - m[0])

    def _mark_first_token(self, seq) -> None:
        """`seq` sampled its first token: close the engine-side split."""
        m = self._first_token_marks.pop(seq.request_id, None)
        if m is None or m[1] is None:
            return
        dt = time.monotonic() - m[1]
        SERVING.engine_prefill.observe(value=dt)
        TRACER.record_span("engine.prefill", m[3], dt, steps=m[2])

    def _ledger_record(self, kind: str, rows: int, rows_live: int,
                       useful: int, padded: int, **stream_kw) -> None:
        """One ledger sample at a commit site. Host-state reads only
        (allocator counters, pool free lists, deque length) — the
        deferred-recorder discipline the ledger's overhead contract and
        the decode hot-path region both require. `stream_kw` carries a
        streamed step's window-pool deltas (stream_hit/late/spilled/
        stalls), an `_engine_step`'s `dense` rows, a window's
        `dev_steps` and the commit's `events` straight through to
        record_step."""
        if not self.ledger.enabled:
            return
        alloc = self.scheduler.allocator
        hp = self.host_pool
        host_used = hp.used if hp is not None else 0
        host_total = hp.capacity if hp is not None else 0
        disk = hp.disk if hp is not None else None
        disk_used = disk.used if disk is not None else 0
        disk_total = disk.capacity if disk is not None else 0
        rc, self._pending_recompiles = self._pending_recompiles, 0
        self.ledger.record_step(
            kind, rows, rows_live, useful, padded,
            alloc.num_pages - alloc.num_free, alloc.num_pages,
            host_used, host_total, disk_used, disk_total,
            len(self.scheduler.waiting), rc, **stream_kw)

    def _run_stream(self, plan: StreamPlan) -> List[StepOutput]:
        """One tiered-KV streamed step (engine/streaming.py): a prefill
        chunk or one decoded token for a sequence whose context exceeds
        the resident HBM budget. The streamer walks the per-layer
        window-pool double buffer; this wrapper owns event emission and
        the ledger sample (kind="stream", with the step's prefetch
        hit/late/spill/stall deltas)."""
        from dynamo_tpu.engine.streaming import STREAM_STATS
        seq = plan.seq
        st0 = (STREAM_STATS.prefetch_hit, STREAM_STATS.prefetch_late,
               STREAM_STATS.pages_spilled, STREAM_STATS.stall_steps)
        self._mark_planned((seq,))
        tok, _ = self._streamer.step(seq)
        events: List[StepOutput] = []
        if tok is not None:
            seq.output.append(tok)
            self._mark_first_token(seq)
            events.append(self._postprocess(seq, tok))
        st1 = (STREAM_STATS.prefetch_hit, STREAM_STATS.prefetch_late,
               STREAM_STATS.pages_spilled, STREAM_STATS.stall_steps)
        self._note_kind(None)
        self._ledger_record(
            "stream", 1, 1, 1 if tok is not None else 0, 1,
            stream_hit=st1[0] - st0[0], stream_late=st1[1] - st0[1],
            stream_spilled=st1[2] - st0[2], stream_stalls=st1[3] - st0[3],
            events=events)
        return events

    def _run_device_step(self, plan, reqs, mixed: bool = False):
        """upload, dispatch and wait of one `_engine_step` program (a
        prefill or mixed step); the caller commits."""
        with self.phases.phase("upload"):
            staged = self._stage_step(plan, reqs, mixed)
        return self._launch_step(staged, "mixed" if mixed else "prefill")

    def _stage_step(self, plan, reqs, mixed: bool = False,
                    after: Optional[dict] = None) -> tuple:
        """Sampling arrays and device staging of one `_engine_step`
        program; runs inside the caller's `upload` phase. `after`: the
        mixed step in flight that `plan` was made behind (_chain_step);
        a decode row whose last token that step is still sampling takes
        it from that step's tokens on the device (`src`: its row there)."""
        temp, top_k, top_p, seeds, counters, min_toks = \
            self._sampling_arrays(reqs, mixed=mixed)
        rp = self._rep_penalty_arrays(reqs, mixed=mixed)
        with_lp = self._wants_logprobs(reqs)
        mm = getattr(plan, "mm_embeds", None) is not None
        src = np.full_like(plan.last_idx, -1)      # [Bb] int32
        prev = self._no_prev
        if after is not None:
            prev = after["prev"]
            src = self._fed_rows(after, (
                seq if plan.is_decode[i] else None
                for i, seq in enumerate(plan.seqs)), len(src))
        # STEP_OPERANDS' order, then the variant's own (__init__)
        small = (plan.tokens, plan.positions, plan.page_table, plan.kv_lens,
                 plan.write_idx, plan.last_idx, temp, top_k, top_p, seeds,
                 counters, min_toks, src)
        if self._state_slots:
            small += (plan.state_slots,)
            real = (plan.write_idx >= 0).sum(axis=1)
            splits = llama.mix_splits(self.model_cfg,
                                      *plan.write_idx.shape)
            self._account_linattn(
                int(real.sum()), int((plan.state_slots >= 0).sum()),
                inplace=int((real == 1).sum()) if splits else 0,
                flat=splits and self._dense_rows(plan) < plan.tokens.size)
        if self._window_pages:
            small += (plan.wtable, plan.woff, plan.wwrite_idx)
            self._account_window_pool(plan)
        own = ()
        if rp is not None:
            small, own = small + (rp[1],), own + (rp[0],)
        if mm:
            small, own = small + (plan.mm_mask,), own + (plan.mm_embeds,)
        key = ("step", rp is not None, with_lp, mm, plan.tokens.shape,
               plan.page_table.shape[1],
               None if rp is None else rp[0].shape[1])
        self._account_attention(
            int(plan.kv_lens.sum()), plan.page_table.size,
            *self._window_reads(plan, plan.kv_lens))
        return key, (prev, *self._stage_operands(small, own)), with_lp

    @staticmethod
    def _fed_rows(after: dict, seqs, rows: int) -> np.ndarray:
        """[rows] int32 `src`: for each decode row of a plan made behind
        the step in flight `after`, the row of that step's tokens its
        last token stands in while it is PENDING_TOKEN on the host, else
        -1 (`after["rows"]`: `_open_mixed`'s or `_open_window`'s)."""
        src = np.full((rows,), -1, np.int32)
        row_of = {id(row[1]): row[0] for row in after["rows"]}
        for i, seq in enumerate(seqs):
            if seq is not None and seq.output \
                    and seq.output[-1] == PENDING_TOKEN:
                src[i] = row_of[id(seq)]
        return src

    def _dense_rows(self, plan) -> int:
        """The token rows the token-wise layers of `plan`'s `_engine_step`
        run over: the flat width where the program takes its compact
        branch, by the predicate the program itself traces
        (llama.step_compaction) on the same `write_idx`; else the grid."""
        compact = None if self.pp > 1 else llama.step_compaction(
            plan.write_idx, self._sp_mesh)
        if compact is not None and compact[1]:
            return compact[0]
        return int(plan.tokens.size)

    def _step_forms(self, plan) -> dict:
        """What the ledger records of the forms `plan`'s `_engine_step`
        took, each decided as the program decides it: `dense`, the token
        rows its token-wise layers ran over (`_dense_rows`), and
        `attn_rows`, whether its attention ran over its real queries
        (a compact step of a shape where llama.step_attention_rows)."""
        dense = self._dense_rows(plan)
        return {"dense": dense, "attn_rows": (
            dense < plan.tokens.size and llama.step_attention_rows(
                self.model_cfg, plan.tokens.shape[1]))}

    def _account_linattn(self, tokens: int, rows: int,
                         window_steps: int = 0, inplace: int = 0,
                         flat: bool = False) -> None:
        """`llm_engine_linattn_*_total`, the series of a recurrent state
        whatever layer keeps it (linear attention; the parallel block's
        state-space mixer; a conv layer's tail; power retention's matrix
        and normaliser), from a step's plan on the host:
        the (token, state layer) state updates of its real tokens,
        which of them rode an `_engine_step` (`window_steps` 0; the
        chunkwise form's, and the one-token rows beside them), which
        were made where the state rests (`kda_step_slots`,
        `ssd_step_slots`, `retention_step_slots`: every token of a decode
        window, and the `inplace` one-token rows of a step that
        `llama.mix_splits`),
        whether such a step's state layers worked over a compact step's
        `flat` token rows (`llama.kda_mix_rows`, `ssm_mix_rows` where
        `_dense_rows` is the flat width; else
        over the grid's), the state bytes its live `rows`
        read and wrote (every touched slot's state, once each way, a
        linear layer and a step), and the device steps that is over. A
        window's bytes and steps are also kept apart, as its experts are
        (`_account_moe`)."""
        stats = self.ledger.stats
        layers = self.model_cfg.num_state_layers
        moved = 2 * rows * self.model_cfg.state_bytes_per_slot()
        stats.linattn_tokens_total += tokens * layers
        stats.linattn_inplace_updates_total += layers * (
            tokens if window_steps else inplace)
        stats.linattn_flat_steps_total += int(flat)
        stats.linattn_state_bytes_total += moved
        stats.linattn_steps_total += window_steps or 1
        if window_steps:
            stats.linattn_window_state_bytes_total += moved
            stats.linattn_window_steps_total += window_steps
        else:
            stats.linattn_chunk_tokens_total += tokens * layers
        stats.state_slots_used = self.scheduler.state_slots.used

    def _account_attention(self, kv_tokens: int, table_pages: int,
                           wkv_tokens: int = 0, wtable_pages: int = 0
                           ) -> None:
        """`llm_engine_attn_kv_tokens_total` / `_slots_total`, from a
        step's plan on the host: the context tokens its real rows attend
        to, and the token slots the gather path reads for them (rows x
        page-table width x page size; a decode window: the base it
        gathers, once a window, whatever its rung). 1 - tokens / slots
        is the share of gathered KV that is bucket padding. They count
        the FULL pool's tables; `llm_engine_attn_kv_window_tokens_total`
        / `_window_slots_total` count the window pool's, for the layers
        that read it (`_window_reads`): window slots / slots is the share
        of a full-length gather that a window layer's gather is. None of
        them moves for a model none of whose layers holds a page: nothing
        attends and nothing is gathered."""
        if not self._paged:
            return
        stats = self.ledger.stats
        stats.attn_kv_tokens_total += kv_tokens
        stats.attn_kv_slots_total += table_pages * self.cfg.page_size
        stats.attn_kv_window_tokens_total += wkv_tokens
        stats.attn_kv_window_slots_total += wtable_pages * self.cfg.page_size

    def _window_reads(self, plan, kv_lens) -> tuple:
        """(tokens, table pages) of a plan's window-pool gather: the keys
        its real rows can see in a sliding layer (their context, at most
        the window), and its table's cells. () without that pool."""
        if not self._window_pages:
            return ()
        seen = np.minimum(kv_lens, self.model_cfg.sliding_window)
        return int(seen.sum()), plan.wtable.size

    def _account_window_pool(self, plan) -> None:
        """The window pool's gauges and counters, from a step's plan:
        pages its live rows hold there (`llm_engine_kv_window_pages_held`:
        the mean over this plan's rows; `_held_sum_total` / `_rows_total`
        the same, summed over plans), pages handed back so far, pages in
        use."""
        stats = self.ledger.stats
        held = [len(s.wpages) for s in plan.seqs if s is not None]
        stats.kv_window_pages_held = sum(held) / max(1, len(held))
        stats.kv_window_pages_held_sum_total += sum(held)
        stats.kv_window_rows_total += len(held)
        stats.kv_window_pages_released_total = \
            self.scheduler.window_released
        alloc = self.scheduler.window_alloc
        stats.kv_window_pages_used = alloc.num_pages - alloc.num_free

    def _stage_operands(self, small: tuple, own: tuple = (),
                        commit: bool = False) -> tuple:
        """THE way a step's host operands reach the device, for every step
        kind: the `small` NumPy arrays (plan and sampling arrays, int32 /
        float32 / bool over one row axis) packed into one int32 buffer
        (pack_operands), put with the few `own` arrays that keep a buffer
        to themselves (a penalty history, image embeddings, a window's
        carry) in ONE `jax.device_put`. Returns what a `_packed` program
        is called with after its params and cache: (layout, packed,
        *own), the arrays device-resident and, like any uncommitted
        array, replicated over the mesh by the call. Runs inside the
        caller's `upload` phase; `host_buffers` counts the buffers."""
        layout, buf = pack_operands(small)
        # `commit` (a decode window): the buffers are put WITH the mesh's
        # replicated sharding, as a window's own outputs are. A chained
        # window is fed the last one's (token, position, counter), a
        # committed array; beside an uncommitted staged one jax traced
        # and XLA compiled every window program twice, the second time
        # wherever the first chained window fell (PERF.md section 6, PR 33)
        staged = jax.device_put((buf, *own),
                                self._replicated if commit else None)
        self.host_buffers += len(staged)
        self.ledger.stats.host_buffers_total += len(staged)
        return (layout, *staged)

    def _dispatch_step(self, staged: tuple, kind: str) -> tuple:
        """dispatch of a staged `_engine_step` program, a step of `kind`
        ("prefill" | "mixed"): (its outputs, still on the device; its
        tokens as the step behind it reads them)."""
        key, args, _ = staged
        # key[1:4] is the variant: (with_rp, with_lp, with_mm)
        fn, args = self._step_fns[key[1:4]], (self.params, self.cache, *args)
        with self._dispatch_phase(key, kind, fn, args):
            *outs, self.cache, aux, prev = fn(*args)
        return (*outs, aux), prev

    def _fetch_step(self, outs: tuple, with_lp: bool = False,
                    in_flight: bool = False):
        """wait of a dispatched `_engine_step` program: its sampled
        tokens. `in_flight`: the step behind it was dispatched before
        this fetch, so the device stays busy through the commit."""
        with self.phases.phase("wait"):
            tokens, lp, top_ids, top_lps, aux = jax.device_get(outs)
        self.phases.device_busy = in_flight
        if aux:
            self._account_moe(aux)
        self._last_logprobs = (lp, top_ids, top_lps) if with_lp else None
        return np.asarray(tokens)

    def _launch_step(self, staged: tuple, kind: str = "prefill"):
        """dispatch + wait of a staged `_engine_step` program."""
        outs, _ = self._dispatch_step(staged, kind)
        return self._fetch_step(outs, with_lp=staged[2])

    def _run_prefill(self, plan: PrefillPlan) -> List[StepOutput]:
        self._mark_planned(plan.seqs)
        sampled = self._run_device_step(plan, plan.seqs)
        with self.phases.phase("commit"):
            return self._commit_prefill(plan, sampled)

    def _commit_prefill(self, plan: PrefillPlan, sampled
                        ) -> List[StepOutput]:
        lps = self._last_logprobs
        events: List[StepOutput] = []
        # rows commit in REVERSE order: each continuing multi-chunk row is
        # re-queued with appendleft, so reverse iteration leaves the
        # earliest-arrived row back at the head (FIFO preserved)
        for i in reversed(range(len(plan.seqs))):
            seq = plan.seqs[i]
            if seq is None:
                continue
            tok = self.scheduler.commit_prefill_row(
                plan, i, int(sampled[i]) if plan.is_last_chunk[i] else None)
            if tok is None:
                continue
            self._mark_first_token(seq)
            if seq.prefill_only:
                # disaggregated prefill: hand the first token to the
                # transfer layer; stop conditions run on the decode side
                events.append(
                    StepOutput(seq.request_id, tok, True, "prefill_done"))
            elif lps is not None:
                events.append(self._postprocess(
                    seq, tok, float(lps[0][i]), lps[1][i], lps[2][i]))
            else:
                events.append(self._postprocess(seq, tok))
        self._note_kind(None)
        self._ledger_record(
            "prefill", len(plan.seqs),
            sum(1 for s in plan.seqs if s is not None),
            sum(plan.n_valid), int(plan.tokens.size),
            **self._step_forms(plan), events=events)
        return events

    def _run_mixed(self, plan: MixedPlan) -> List[StepOutput]:
        """One fused prefill+decode step (docs/PERF.md): decode rows and
        prefill chunk rows share a single [Bb, Tb] forward+sample program
        (the same _step_fns variant prefill uses — a decode row is a
        one-token causal chunk, so the program set gains no new member).

        Exactness: decode rows sample through the identical
        sample_logits tail with the same (seed, counter) the decode
        window would use, so greedy and seeded-sampled streams are
        token-identical to the alternating scheduler (CPU/f32 exact; on
        TPU bf16 the prefill-shaped forward and the window program
        differ arithmetically at near-tie level, the same caveat as the
        spec-decode verify path).

        Where mixed steps may chain (_chain_ok) the step is dispatched
        and left in flight: the next call, `_chain_step`, commits it (its
        events surface there, as a primed window's do) behind the
        dispatch of the step after it where there is one. Any other step
        takes the synchronous lines below."""
        self._mark_planned(plan.seqs)
        if self._ahead_failed:
            self._ahead_failed = False
            self.mixed_steps_replanned += 1
        if self._chain_ok(plan.seqs):
            self._flight = self._launch_mixed(plan)
            return []
        sampled = self._run_device_step(plan, plan.seqs, mixed=True)
        with self.phases.phase("commit"):
            return self._commit_mixed(plan, sampled)

    # -- the mixed chain -----------------------------------------------------

    def _chain_ok(self, seqs=()) -> bool:
        """May a mixed step be dispatched before the mixed step in front
        of it is fetched? As `_pipeline_ok` for windows: not under pp or
        a spec-decode hand-off, not beside streamed decode or pending
        offloads / onboards / pool injects, and not while any sequence
        the engine holds (running, queued, or among `seqs`, a plan's
        rows) wants what the chain does not carry: logprobs, a penalty
        history, image embeddings, a prefill-only hand-over. All of it
        is read from the queues; such a step runs as it always did."""
        sch = self.scheduler
        if self.cfg.pipeline_depth < 2 or self.pp > 1 \
                or self._verify_fn is not None or self._draft is not None:
            return False
        if sch.stream_active or sch.pending_onboards \
                or sch.pending_pool_injects or self._pending_offloads \
                or sch.overlap_gates:
            return False
        params = sch.params
        for group in (seqs, sch.running, sch.waiting):
            for seq in group:
                if seq is None:
                    continue
                p = params[seq.request_id]
                if p.logprobs is not None or p.repetition_penalty != 1.0 \
                        or seq.mm_spans or seq.prefill_only:
                    return False
        return True

    def _launch_mixed(self, plan: MixedPlan,
                      after: Optional[dict] = None) -> dict:
        """upload and dispatch of a mixed step that is left in flight,
        its outputs on their way to the host. `dead`: prefill rows an
        abort ended under it; `rows`: `_open_mixed`'s."""
        with self.phases.phase("upload"):
            staged = self._stage_step(plan, plan.seqs, mixed=True,
                                      after=after)
        outs, prev = self._dispatch_step(staged, "mixed")
        self._copy_outs_async(outs)
        # the decode rows advance outside the window program: any saved
        # device-resident window carry is stale
        self._dec_state = None
        return {"plan": plan, "key": staged[0], "outs": outs, "prev": prev,
                "dead": set(), "rows": (), "ahead": False}

    def _chain_step(self) -> List[StepOutput]:
        """Advance the chain by one step() call from a mixed step N in
        flight (`self._flight`: the call before dispatched it):

        1. open N's commit (_open_mixed): everything the host knows of
           it without its tokens. Counts advance, a last chunk's row
           takes its slot, a row whose token is its last by `max_tokens`
           gives up slot and pages; the tokens themselves stand as
           PENDING_TOKEN;
        2. plan the step after N on that state with the ordinary planner
           (_plan_ahead: a mixed step while a prompt waits, else the
           decode window), so an end by length, a first token and an
           arrival are simply what the plan finds, and dispatch it: a
           row that decodes on reads its token from N's on the device;
        3. fetch N (the one host sync) and close its commit
           (_close_mixed) while that step runs: the tokens take their
           places, stops are seen, the events go out.

        An end the host could not foresee (a stop id, EOS) and an abort
        leave the step behind N in flight with a row nobody holds any
        more: it is committed for the rows still live (_mixed_live,
        _live_rows) and never thrown away, because a recurrent state it
        advanced cannot be run twice (docs/PERF.md has the exactness
        argument). Where no step can go ahead, 1 and 3 are the
        synchronous commit. Every phase is entered at most once a call,
        as everywhere."""
        flight, self._flight = self._flight, None
        with self.phases.phase("plan"):
            self._open_mixed(flight)
            plan = self._plan_ahead() if self._chain_ok() else None
        if plan is not None:
            self._launch_ahead(plan, flight)
            self.mixed_steps_chained += self._flight is not None
        # this call commits N, whatever it dispatched
        self._call_key = flight["key"]
        sampled = self._fetch_step(flight["outs"],
                                   in_flight=plan is not None)
        with self.phases.phase("commit"):
            events = self._close_mixed(flight, sampled)
        self._settle_window()
        return events

    def _plan_ahead(self):
        """The step to dispatch behind the step in flight, planned on
        the state its open commit leaves: a MixedPlan while a prompt
        waits, else a DecodePlan that may enter the pipeline
        (_pipeline_ok), else None: the planner would have had to preempt
        or found no row. The next call then plans with nothing in
        flight, as it always did. For the caller that `_chain_ok()`
        allows, inside its `plan` phase."""
        sch = self.scheduler
        if sch.waiting:
            plan = sch.schedule_ahead()
            self._process_offloads()
            self._process_onboards()
            self._process_pool_injects()
            self._ahead_failed = plan is None
            return plan
        plan = sch.schedule_decode_ahead()
        return plan if plan is not None and self._pipeline_ok(plan) \
            else None

    def _launch_ahead(self, plan, after: dict) -> None:
        """upload and dispatch of `plan` behind the step in flight
        `after`, whichever kind each is: the new step is left in flight
        in its own place (`self._flight`, `self._pipeline`), marked
        `ahead`."""
        self.step_count += 1
        # the rows advance outside the last window's carry
        self._dec_state = None
        if isinstance(plan, MixedPlan):
            self._mark_planned(plan.seqs)
            self._flight = self._launch_mixed(plan, after=after)
            self._flight["ahead"] = True
        else:
            self._prime_pipeline(plan, after=after)

    def _settle_window(self) -> None:
        """The commit just closed may have ended a row of the window
        dispatched behind it (a stop id, EOS): the window stays in
        flight for the rows still live, flagged as a follow-up is that a
        commit ended a row under (_pipeline_step), or is let go where no
        row of it lives on."""
        pend = self._pipeline
        if pend is None or self._membership_intact(pend["plan"]):
            return
        self.pipeline_fallbacks += 1
        self._dec_state = None
        if any(self._live_rows(pend["plan"])):
            pend["drain"] = pend["reconciled"] = True
        else:
            self.window_steps_discarded += pend["staged"]["nw"]
            self._pipeline = None

    def _note_kind(self, kind: Optional[str], ahead: bool = False) -> None:
        """A device step of `kind` ("mixed", "decode": a window; None:
        any other) is being committed. `handovers` counts the commits
        whose kind differs from the commit before them, mixed against
        window; `handovers_chained` those of them that were dispatched
        before the step in front of them was fetched (`ahead`)."""
        last, self._last_kind = self._last_kind, kind
        if last and kind and last != kind:
            self.handovers += 1
            self.handovers_chained += bool(ahead)

    def _mixed_live(self, flight: dict) -> List[bool]:
        """Which rows of the mixed step in flight still hold the sequence
        they were planned with, the rows it is committed for (`_live_rows`
        for a window): a decode row whose slot still holds it, a prefill
        row that no abort ended."""
        plan, running = flight["plan"], self.scheduler.running
        return [seq is not None and (
                    seq.slot >= 0 and running[seq.slot] is seq
                    if plan.is_decode[i] else i not in flight["dead"])
                for i, seq in enumerate(plan.seqs)]

    def _open_mixed(self, flight: dict) -> None:
        """The commit of the mixed step in flight, as far as the host
        knows it before the step's tokens: `_commit_mixed`'s scheduler
        calls in `_commit_mixed`'s order, each sampled token standing as
        PENDING_TOKEN. A row whose token is its last by `max_tokens`
        ends here, slot, state and pages, exactly as the closed commit
        would end it a moment later; its event waits for the token.
        `flight["rows"]`: (row, sequence, its params, where in its
        output the token goes, ended) of every row that sampled."""
        plan, sch = flight["plan"], self.scheduler
        live = self._mixed_live(flight)
        rows = flight["rows"] = []

        def opened(i: int, seq: SequenceState, p) -> None:
            ended = len(seq.output) >= p.max_tokens
            if ended:
                sch.finish(seq)
            rows.append((i, seq, p, len(seq.output) - 1, ended))

        for i, seq in enumerate(plan.seqs):
            if live[i] and plan.is_decode[i]:
                p = sch.params[seq.request_id]
                sch.commit_decode_token(seq, PENDING_TOKEN)
                opened(i, seq, p)
        for i in reversed(range(len(plan.seqs))):
            seq = plan.seqs[i]
            if not live[i] or plan.is_decode[i]:
                continue
            p = sch.params[seq.request_id]
            if sch.commit_prefill_row(
                    plan, i, PENDING_TOKEN if plan.is_last_chunk[i]
                    else None) is not None:
                opened(i, seq, p)
        flight["live"] = sum(live)

    def _close_mixed(self, flight: dict, sampled) -> List[StepOutput]:
        """The rest of that commit, the step's tokens in hand: each takes
        its place in its sequence's output, stop conditions run on it,
        and the events go out in `_commit_mixed`'s order."""
        plan = flight["plan"]
        events: List[StepOutput] = []
        for i, seq, p, at, ended in flight["rows"]:
            tok = seq.output[at] = int(sampled[i])
            if not plan.is_decode[i]:
                self._mark_first_token(seq)
            events.append(self._postprocess(
                seq, tok, opened=(p, ended, at + 1)))
        self.mixed_steps += 1
        self._note_kind("mixed", flight["ahead"])
        self._ledger_record(
            "mixed", len(plan.seqs), flight["live"], sum(plan.n_valid),
            int(plan.tokens.size), **self._step_forms(plan),
            events=events)
        return events

    def _commit_mixed(self, plan: MixedPlan, sampled) -> List[StepOutput]:
        lps = self._last_logprobs
        events: List[StepOutput] = []
        # decode rows first (slot order, the decode path's commit order);
        # a finish here frees slots the prefill rows never relied on —
        # their slot reservations were taken at planning time
        for i, seq in enumerate(plan.seqs):
            if seq is None or not plan.is_decode[i]:
                continue
            self.scheduler.commit_decode_token(seq, int(sampled[i]))
            if lps is not None:
                events.append(self._postprocess(
                    seq, seq.output[-1], float(lps[0][i]), lps[1][i],
                    lps[2][i]))
            else:
                events.append(self._postprocess(seq, seq.output[-1]))
        # prefill rows commit in REVERSE order: continuing multi-chunk
        # rows re-queue with appendleft, so reverse iteration keeps the
        # earliest-arrived row at the head (FIFO, as _run_prefill)
        for i in reversed(range(len(plan.seqs))):
            seq = plan.seqs[i]
            if seq is None or plan.is_decode[i]:
                continue
            tok = self.scheduler.commit_prefill_row(
                plan, i, int(sampled[i]) if plan.is_last_chunk[i] else None)
            if tok is None:
                continue
            self._mark_first_token(seq)
            if seq.prefill_only:
                events.append(
                    StepOutput(seq.request_id, tok, True, "prefill_done"))
            elif lps is not None:
                events.append(self._postprocess(
                    seq, tok, float(lps[0][i]), lps[1][i], lps[2][i]))
            else:
                events.append(self._postprocess(seq, tok))
        # the decode rows advanced outside the window program: any saved
        # device-resident window carry (token/position/counter) is stale
        self._dec_state = None
        self.mixed_steps += 1
        self._note_kind("mixed")
        self._ledger_record(
            "mixed", len(plan.seqs),
            sum(1 for s in plan.seqs if s is not None),
            sum(plan.n_valid), int(plan.tokens.size),
            **self._step_forms(plan), events=events)
        return events

    def _run_decode(self, plan: DecodePlan) -> List[StepOutput]:
        if self.pp > 1:
            return self._run_decode_pp(plan)
        with self.phases.phase("upload"):
            samp = self._sampling_arrays(plan.seqs)
            rp = self._rep_penalty_arrays(plan.seqs)
            with_lp = self._wants_logprobs(plan.seqs)
            greedy = all(t <= 0.0 for t in samp[0])
            drafts = self._spec_drafts(plan, greedy, with_lp, rp)
            if drafts is not None:
                block = self._stage_spec(plan, drafts, samp[4], samp[5])
            else:
                staged = self._stage_window(plan, samp, rp, with_lp, greedy)
        if drafts is not None:
            return self._run_spec_decode(plan, drafts, block)
        outs, nxt = self._dispatch_staged(staged, staged["first"])
        self._dec_state = {"sig": staged["sig"], "dev": staged["dev"],
                           "next": nxt}
        return self._fetch_and_commit(plan, outs)

    def _spec_drafts(self, plan: DecodePlan, greedy: bool, with_lp: bool,
                     rp) -> Optional[list]:
        """Drafts to verify in place of this plan's window, or None.
        Speculative decoding takes greedy plans whose drafts beat the
        window's dispatch amortization (acceptance-ema cost gate); plans
        the verify program doesn't model (sampling, logprobs, penalties),
        draft-less steps and low-expected-acceptance steps fall through
        to the window. Composes with pp: the verify block is one
        prefill-shaped pp_forward, so the same gate serves both paths."""
        if (self._verify_fn is None or not greedy or with_lp
                or rp is not None):
            return None
        if self._draft is not None:
            # draft-model mode: the proposal budget is known up front,
            # so the gate runs before any draft compute
            caps = self._draft.caps(plan)
            if sum(caps) and self._spec_worthwhile(plan, sum(caps)):
                return self._draft.propose(plan, caps)
            return None
        if self._spec_bound_ok(plan):
            drafts = self._gather_drafts(plan)
            if any(drafts):
                if self._spec_worthwhile(plan, sum(len(d) for d in drafts)):
                    return drafts
            elif self._spec_gate_skips >= self.cfg.spec_probe_every:
                # a probe-granted scan that found no drafts still spends
                # the probe: otherwise the counter sticks at the
                # threshold and the precheck admits the scan on every
                # step forever (code-review r5)
                self._spec_gate_skips = 0
        return None

    # -- decode window staging / dispatch ------------------------------------
    # dynalint: hot-path-begin — every host op between two decode-window
    # dispatches is serving latency the device cannot hide; blocking syncs
    # here need an explicit `# dynalint: sync-point` justification (R8)

    def _window_rung(self, plan: DecodePlan) -> int:
        """Smallest compiled ladder rung covering the plan's window."""
        return next((w for w in reversed(self._window_sizes)
                     if w >= max(1, plan.n_window)), self._window_sizes[0])

    def _stage_window(self, plan: DecodePlan, samp, rp, with_lp: bool,
                      greedy: bool, after: Optional[dict] = None) -> dict:
        """Stage the device-side plan arrays for a decode window.

        Split-KV base width (VERDICT r3 missing #2): the base gather covers
        only the VALID kv at window start, sliced from the page table at
        the bucket of the true page count — not the admission-time
        allocation width, which reserves pages for max_tokens and made
        attention read up to 2x the valid KV.

        Device-resident decode state: if the slot set + page allocation are
        unchanged since the last window (and no penalty hist needs
        refreshing), reuse the device plan arrays and feed the last
        window's final (token, position, counter) device arrays straight
        back in — steady-state windows then upload NOTHING. Runs inside
        the caller's `upload` phase.

        `after`: the step in flight the plan was made behind
        (_launch_ahead). A row whose last token that step is still
        sampling takes it from that step's tokens on the device: the
        staged carry has a fourth column, `src` (_fed_rows), and
        `_carry_behind` makes the window's [S, 3] of it there."""
        temp, top_k, top_p, seeds, counters, min_toks = samp
        ps = self.cfg.page_size
        if self._window_pages:
            self._account_window_pool(plan)
        base_lens = np.clip(plan.positions[:, 0], 0, plan.max_pos + 1)
        base_pages = max(1, int(-(-int(base_lens.max()) // ps)))
        base_pb = min(next_bucket(base_pages, self.scheduler.page_buckets),
                      plan.page_table.shape[1])
        if not self._paged:
            # no layer gathers a base: the live-KV bucket names no program
            # of such a model, and its base table keeps the plan's width
            base_pb = plan.page_table.shape[1]
        sig = (tuple((s.request_id, s.epoch) if s else None
                     for s in plan.seqs),
               tuple((len(s.pages), s.wfirst, len(s.wpages)) if s else 0
                     for s in plan.seqs),
               plan.page_table.shape[1], base_pb, plan.stop_ids.shape[1],
               rp is None, with_lp, greedy)
        st = self._dec_state
        if st is not None and st["sig"] == sig and rp is None:
            dev = st["dev"]
            first = st["next"]
        else:
            ign = np.array([
                bool(self.scheduler.params[s.request_id].ignore_eos)
                if s is not None else True for s in plan.seqs])
            # WINDOW_OPERANDS' order (+ the penalty pair); the carry
            # keeps a buffer of its own: a chained window is handed the
            # device's
            small = (plan.page_table, plan.page_table[:, :base_pb],
                     plan.max_pos, temp, top_k, top_p, seeds, min_toks,
                     ign, plan.stop_ids)
            if self._state_slots:
                small += (plan.state_slots,)
            if self._window_pages:
                small += (plan.wtable, plan.woff)
            carry = self._window_carry(plan, counters)
            if after is not None:
                carry = np.concatenate((carry, self._fed_rows(
                    after, plan.seqs, len(plan.seqs))[:, None]), axis=1)
            own = (carry,)
            if rp is not None:
                small, own = small + (rp[1],), (rp[0],) + own
            *dev, first = self._stage_operands(small, own, commit=True)
            if after is not None:
                first = self._carry_fn(after["prev"], first)
            self.decode_plan_uploads += 1
        nw = self._window_rung(plan)
        pregather = llama._decode_kernel_mode(self.model_cfg) is None
        return {"sig": sig, "dev": dev, "first": first, "nw": nw,
                "key": (rp is not None, with_lp, greedy, nw),
                # recompile detection (_dispatch_phase): the decode-window
                # program is keyed by its variant grid entry plus every
                # bucketed dim
                "program": ("window", rp is not None, with_lp, greedy,
                            nw, len(plan.seqs),
                            plan.page_table.shape[1], base_pb,
                            plan.stop_ids.shape[1]),
                # per-window attribution tag (`decode_kernel_tag`):
                # which attention path this window's one device program
                # runs
                "tag": "gather" if pregather else "ragged",
                # valid-KV capacity of the staged base table; the kernel
                # path streams from the global cache and has no base cap
                "base_cap": base_pb * ps if pregather else None,
                # (context tokens, table pages) of the base this window
                # gathers, once: _account_attention, at dispatch
                "attn": (int(base_lens.sum()), len(plan.seqs) * base_pb)
                + self._window_reads(plan, base_lens),
                # (tokens, rows) of a window's state updates:
                # _account_linattn, at dispatch
                "linattn": (nw * sum(s is not None for s in plan.seqs),) * 2
                + (nw,) if self._state_slots else None,
                "pp": False}

    @staticmethod
    def _window_carry(plan: DecodePlan, counters) -> np.ndarray:
        """[S, 3] int32: the (token, position, counter) a window starts
        from, the form in which a window program takes its carry and
        hands on the next."""
        return np.stack((plan.tokens[:, 0], plan.positions[:, 0], counters),
                        axis=1)

    def _stage_pp_window(self, plan: DecodePlan, samp,
                         greedy: bool) -> dict:
        """Stage a pipeline-parallel decode window (models/pp.py). Same
        device-resident reuse contract as _stage_window: an unchanged slot
        set + page allocation feeds the previous window's (token, position,
        counter) carry back in with zero host array uploads."""
        temp, top_k, top_p, seeds, counters, min_toks = samp
        sig = (tuple((s.request_id, s.epoch) if s else None
                     for s in plan.seqs),
               tuple(len(s.pages) if s else 0 for s in plan.seqs),
               plan.page_table.shape[1], plan.stop_ids.shape[1],
               "pp", greedy)
        st = self._dec_state
        if st is not None and st["sig"] == sig:
            dev = st["dev"]
            first = st["next"]
        else:
            ign = np.array([
                bool(self.scheduler.params[s.request_id].ignore_eos)
                if s is not None else True for s in plan.seqs])
            # PP_WINDOW_OPERANDS' order
            *dev, first = self._stage_operands(
                (plan.page_table, plan.max_pos, min_toks, ign,
                 plan.stop_ids, temp, top_k, top_p, seeds),
                (self._window_carry(plan, counters),))
            self.decode_plan_uploads += 1
        nw = self._window_rung(plan)
        return {"sig": sig, "dev": dev, "first": first, "nw": nw,
                "key": (nw, greedy),
                "program": ("ppwindow", greedy, nw, len(plan.seqs),
                            plan.page_table.shape[1],
                            plan.stop_ids.shape[1]),
                "tag": "pp",
                "base_cap": None, "pp": True}

    def _dispatch_staged(self, staged: dict, carry):
        """Dispatch one decode window from its staged device operands
        (`staged["dev"]`: _stage_operands' layout, packed plan and, on a
        penalty plan, history) + a [S, 3] (token, position, counter)
        carry. Returns (outs, next_carry) with outs still ON DEVICE — the
        caller decides when to sync."""
        fn = (self._pp_decode_fns if staged["pp"]
              else self._decode_fns)[staged["key"]]
        args = (self.params, self.cache, carry, *staged["dev"])
        with self._dispatch_phase(staged["program"], "window", fn, args):
            if staged["pp"]:
                toks, self.cache, nxt = fn(*args)
                outs = (toks, None, None, None, {})
            else:
                toks, lps, top_ids, top_lps, self.cache, aux, nxt = \
                    fn(*args)
                outs = (toks, lps, top_ids, top_lps, aux)
        self.decode_windows += 1
        if "attn" in staged:
            self._account_attention(*staged["attn"])
        if staged.get("linattn"):
            self._account_linattn(*staged["linattn"])
        # one window == one device program launch: attention (ragged
        # kernel or gather) + sampling tail all inside it: dispatches /
        # windows must hold at exactly 1.0 on the common path
        self.decode_dispatches += 1
        self.decode_kernel_tag = staged.get("tag", "")
        return outs, nxt

    def _fetch_and_commit(self, plan: DecodePlan, outs,
                          in_flight: bool = False,
                          reconciled: bool = False, ahead: bool = False,
                          opened: Optional[dict] = None
                          ) -> List[StepOutput]:
        """Blocking output fetch + host commit for one window.
        `in_flight`: the step behind it was dispatched before this fetch,
        so the device stays busy through the commit. `reconciled`: the
        window ran under a commit that ended one of its rows. `ahead`:
        it was itself dispatched before the step in front of it was
        fetched. `opened`: `_open_window`'s, where its commit was opened
        before this fetch."""
        with self.phases.phase("wait"):
            toks, lps, top_ids, top_lps, aux = \
                jax.device_get(outs)  # dynalint: sync-point — the one
            #   intended host sync per decode window: [N, S] sampled ids
            #   (+ optional logprobs) are all that crosses to host
        self.phases.device_busy = in_flight
        self.decode_host_syncs += 1
        if aux:
            self._account_moe(aux, window=True)
        with self.phases.phase("commit"):
            return self._commit_window(plan, np.asarray(toks), lps,
                                       top_ids, top_lps, reconciled, ahead,
                                       opened)

    # -- overlapped decode pipeline ------------------------------------------

    def _pipeline_ok(self, plan) -> bool:
        """May `plan` enter the overlapped pipeline? Conservative: only
        hot-path windows (no logprobs / penalties / spec-decode handoff),
        only when a follow-up window could actually be dispatched off this
        plan's staged page tables (otherwise deferring the commit buys no
        overlap and only delays events)."""
        if self.cfg.pipeline_depth < 2 or not isinstance(plan, DecodePlan):
            return False
        if self._verify_fn is not None or self._draft is not None:
            return False   # spec-decode handoff stays synchronous
        if self.pp > 1 and plan.n_window <= 1:
            return False   # pp per-token fallback path
        if self.scheduler.waiting or self.scheduler.pending_onboards \
                or self.scheduler.pending_pool_injects \
                or self._pending_offloads:
            return False
        if self.scheduler.stream_active:
            return False   # streamed steps interleave; don't lock them out
        if self._wants_logprobs(plan.seqs) \
                or self._rep_penalty_arrays(plan.seqs) is not None:
            return False
        return self._followup_fits(plan, next_index=1)

    def _followup_fits(self, plan: DecodePlan, next_index: int) -> bool:
        """Can speculative window `next_index` (0 = the plan's own window)
        run entirely against the plan's staged page tables? Its writes
        must land in pages listed at staging time, and (pregather path)
        its valid-KV prefix must fit the staged base-table width."""
        nw = self._window_rung(plan)
        live = np.array([s is not None for s in plan.seqs])
        if not live.any():
            return False
        pos0 = plan.positions[:, 0]
        start = pos0 + next_index * nw
        if np.all(start[live] > plan.max_pos[live]):
            return False   # every slot is out of budget: pure garbage
        covered = np.array([len(s.pages) if s is not None else 0
                            for s in plan.seqs]) * self.cfg.page_size
        # exclusive end of this window's writes, clamped by each request's
        # admission budget (writes beyond max_pos are dropped on device)
        need = np.minimum(start + nw, plan.max_pos + 1)
        if np.any(need[live] > covered[live]):
            return False
        if not self.pp > 1:
            pregather = llama._decode_kernel_mode(self.model_cfg) is None
            if pregather:
                ps = self.cfg.page_size
                base_lens = np.clip(plan.positions[:, 0], 0,
                                    plan.max_pos + 1)
                base_pb = min(
                    next_bucket(max(1, int(-(-int(base_lens.max()) // ps))),
                                self.scheduler.page_buckets),
                    plan.page_table.shape[1])
                base_need = np.clip(start, 0, plan.max_pos + 1)
                if int(base_need[live].max()) > base_pb * ps:
                    return False
        return True

    def _prime_pipeline(self, plan: DecodePlan,
                        after: Optional[dict] = None
                        ) -> Optional[List[StepOutput]]:
        """Dispatch `plan`'s window and DEFER its commit: outputs start an
        async device->host copy and the events surface on the next step()
        call, which dispatches the follow-up window before fetching them.
        Returns None when the plan turns out ineligible (caller falls back
        to the synchronous path). `after`: the step in flight the plan
        was made behind (_launch_ahead, _stage_window)."""
        with self.phases.phase("upload"):
            samp = self._sampling_arrays(plan.seqs)
            greedy = self._samp_cache.all_greedy
            if self.pp > 1:
                staged = self._stage_pp_window(plan, samp, greedy)
            else:
                staged = self._stage_window(plan, samp, None, False, greedy,
                                            after=after)
        outs, nxt = self._dispatch_staged(staged, staged["first"])
        self._dec_state = {"sig": staged["sig"], "dev": staged["dev"],
                           "next": nxt}
        self._copy_outs_async(outs)
        self._pipeline = {
            "plan": plan, "staged": staged, "outs": outs, "nxt": nxt,
            # index of the in-flight window relative to the staged plan:
            # 0 = the plan's own window, each follow-up increments it
            "j": 0,
            "t_dispatch": time.perf_counter(),
            # dispatched before the step in front of it was fetched
            "ahead": after is not None,
        }
        return []

    @staticmethod
    def _copy_outs_async(outs) -> None:
        """Start the device->host transfer of window outputs without
        blocking: by the time the next step() fetches them the copy has
        ridden the device's execution of the window itself."""
        for leaf in jax.tree.leaves(outs):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()

    def _membership_intact(self, plan: DecodePlan) -> bool:
        """True while every ROW of `plan` still maps to the same live
        sequence object (no finish, abort, or preemption since staging) —
        the validity condition for results computed off the staged state.

        Deliberately per-row (the mixed-step membership-guard extension):
        an admission that fills a slot the plan staged as PADDING does
        not invalidate the in-flight window — its results for the staged
        rows are exact, the padding row computed nothing (max_pos=-1
        keeps it !alive with no KV writes) — so the window is COMMITTED,
        not discarded. Whether the pipeline may keep chaining off the
        staged plan is a separate question (_slots_grown): a grown slot
        set needs a re-plan so the new arrival joins the next window."""
        running = self.scheduler.running
        for i, seq in enumerate(plan.seqs):
            if seq is not None and running[i] is not seq:
                return False
        return True

    def _live_rows(self, plan: DecodePlan) -> List[bool]:
        """Which rows of `plan` still hold the sequence they were staged
        with: the rows a window's results may be committed for."""
        running = self.scheduler.running
        return [seq is not None and running[i] is seq
                for i, seq in enumerate(plan.seqs)]

    def _slots_grown(self, plan: DecodePlan) -> bool:
        """A slot the staged plan held as padding is now occupied (an
        admission landed since staging): in-flight results stay valid,
        but further windows off this plan would starve the newcomer."""
        running = self.scheduler.running
        return any(seq is None and running[i] is not None
                   for i, seq in enumerate(plan.seqs))

    def _pipeline_step(self) -> List[StepOutput]:
        """Advance the two-deep decode pipeline by one step():

        1. dispatch the follow-up window (device carry only — zero host
           array uploads) while the in-flight window's outputs are still
           transferring;
        2. fetch the in-flight window's outputs (the one host sync);
        3. commit them on host — CONCURRENT with device execution of the
           follow-up dispatched in (1);
        4. reconcile: if the commit ended a row (stop/eos/length), the
           follow-up still holds, for every row that lives on, exactly
           what a re-plan would compute next (rows of a window do not see
           each other). It stays in flight flagged `drain`: the next
           step() commits it for the rows whose slot still holds the
           same sequence (_commit_window's identity guard), and the step
           after that re-plans. What the row that ended wrote meanwhile
           lands past its committed positions, in pages the staged table
           owned (docs/PERF.md has the full exactness argument).

        Where a request waits, the mixed step that takes it in is
        planned BEFORE the window is fetched, as the step after a mixed
        step is (_chain_step): the window's commit is opened
        (_open_window: the tokens of its rows stand as PENDING_TOKEN, a
        row whose budget ends inside it gives up its slot), the ordinary
        planner plans on that state (_plan_ahead), the step is
        dispatched with its decode rows' tokens read from this window's
        carry on the device, and 2 and 3 then close the commit while it
        runs. A stop the host could not foresee cuts its row back
        (_place_token); the step behind is committed for the rows still
        live. With nothing waiting a window that ends a chain is
        committed with nothing behind it, as ever: a row that ended is a
        slot whose next request is on its way, and a window made ahead
        would make it wait (PERF.md section 6, PR 46)."""
        pend, self._pipeline = self._pipeline, None
        self.step_count += 1
        plan, staged = pend["plan"], pend["staged"]
        follow = ahead = after = None
        with self.phases.phase("plan"):
            self._process_offloads()
            self._process_onboards()
            self._process_pool_injects()
            if pend.get("drain"):
                chain = False   # flagged reconcile: commit, then re-plan
            elif self.scheduler.waiting or self.scheduler.pending_onboards \
                    or self.scheduler.pending_pool_injects:
                # admission pending: no further window off this plan. The
                # in-flight window is COMMITTED below (reconciled, never
                # discarded) and the next step is the mixed prefill+decode
                # step that takes the arrival in
                chain = False
            elif not self._membership_intact(plan):
                chain = False   # abort mid-window: commit what's valid
            elif self._slots_grown(plan):
                # an admission filled a staged-padding slot: the newcomer
                # needs the next plan, stop chaining
                chain = False
            else:
                chain = self._followup_fits(plan, pend["j"] + 1)
            if not chain and self.scheduler.waiting and self._chain_ok():
                after = self._open_window(pend)
                ahead = self._plan_ahead()
        if chain:
            follow_outs, follow_nxt = self._dispatch_staged(
                staged, pend["nxt"])
            self._copy_outs_async(follow_outs)
            follow = {"plan": plan, "staged": staged, "outs": follow_outs,
                      "nxt": follow_nxt, "j": pend["j"] + 1,
                      "t_dispatch": time.perf_counter(), "ahead": False}
        elif ahead is not None:
            after["prev"] = self._prev_fn(pend["nxt"])
            self._launch_ahead(ahead, after)
        # this call commits the staged window, whatever it dispatched
        self._call_key = staged["program"]
        events = self._fetch_and_commit(
            plan, pend["outs"],
            in_flight=follow is not None or ahead is not None,
            reconciled=pend.get("reconciled", False), ahead=pend["ahead"],
            opened=after)
        self.pipeline_windows += 1
        if after is not None:
            # the commit ran while the step made ahead did, where one was
            self.pipeline_overlapped += ahead is not None
            if ahead is None and not self._membership_intact(plan):
                self._dec_state = None
            return events
        intact = self._membership_intact(plan)
        if follow is not None:
            if intact:
                # true overlap: the commit above ran while the follow-up
                # executed on device
                self.pipeline_overlapped += 1
                if self._slots_grown(plan):
                    # reconcile, don't discard: the follow-up's results
                    # are exact for every staged row (the newly filled
                    # slot was padding — no compute, no KV writes), so
                    # commit it next step, then re-plan so the arrival
                    # joins the decode set
                    follow["drain"] = True
                self._pipeline = follow
                self._dec_state = {"sig": staged["sig"],
                                   "dev": staged["dev"],
                                   "next": follow["nxt"]}
            else:
                # the commit ended a row under the follow-up. For the
                # rows that live on its results are exact (rows are
                # independent of each other, the dropless dispatch and a
                # per-row expert capacity included), its KV rows rest
                # where a re-run would write the same values, and a
                # recurrent state it advanced cannot be run over twice:
                # commit it next step for those rows, then re-plan, as
                # for a grown slot set. The slot set changed, so the
                # next window stages afresh
                self._pipeline = follow
                self._settle_window()
        elif not intact:
            self._dec_state = None
        return events

    def _open_window(self, pend: dict) -> dict:
        """The commit of the window in flight, as far as the host knows
        it before the window's tokens: `_commit_window`'s scheduler calls
        in `_commit_window`'s order, step by step over the rows still
        live, each sampled token standing as PENDING_TOKEN. A row whose
        budget ends inside the window stops there and gives up its
        decode slot and its state slot (Scheduler.release_row); its pages
        go when the window's tokens are known (_place_token), because
        the pages those tokens fill are sealed by content. Returns what
        the step planned behind it is staged from and the commit is
        closed with: `rows` (row, sequence, its params, where in its
        output the window's first token goes, how many it gets, ended),
        `at` (the same by row) and `live`."""
        plan, sch = pend["plan"], self.scheduler
        live = self._live_rows(plan)
        rows = [[i, seq, sch.params[seq.request_id], len(seq.output), 0,
                 False] for i, seq in enumerate(plan.seqs) if live[i]]
        for _ in range(pend["staged"]["nw"]):
            for row in rows:
                _, seq, p, _, _, ended = row
                if ended:
                    continue
                sch.commit_decode_token(seq, PENDING_TOKEN)
                row[4] += 1
                if len(seq.output) >= p.max_tokens:
                    row[5] = True
                    sch.release_row(seq)
        return {"rows": rows, "at": {row[0]: row for row in rows},
                "live": live}

    def _place_token(self, row: list, step: int, tok: int) -> StepOutput:
        """Token `step` of an opened window's row takes its place: stop
        conditions run on it as on any token. At the row's end, by its
        budget (seen when the commit was opened) or by a stop the host
        could not foresee, which cuts the row back to this token, the
        sequence is finished, the pages the window's tokens filled
        sealed first; a row that goes on has them sealed at the
        window's last token."""
        _, seq, p, at, count, _ = row
        sch = self.scheduler
        seq.output[at + step] = tok
        ev = self._postprocess(seq, tok, opened=(p, True, at + step + 1))
        last = step == count - 1
        if ev.finished and not last:
            del seq.output[at + step + 1:]
            seq.num_cached -= count - 1 - step
            seq.num_computed -= count - 1 - step
        if ev.finished or last:
            sch._seal_full_pages(seq)
        if ev.finished:
            sch.finish(seq)
        return ev

    # dynalint: hot-path-end

    def _gather_drafts(self, plan: DecodePlan) -> list:
        """Per-slot prompt-lookup proposals, clamped to the shared
        draft_cap budget (spec.py: page allocation ∧ max_tokens) and
        truncated to in-vocab ids (multimodal histories hold salt ids
        the verify embedding must never see — ADVICE r5 high)."""
        from dynamo_tpu.engine.spec import draft_cap, ngram_propose
        ps = self.cfg.page_size
        drafts: list = []
        for i, seq in enumerate(plan.seqs):
            d_max = (draft_cap(seq, plan.max_pos[i], ps, self.cfg.spec_k)
                     if seq is not None else 0)
            if d_max <= 0:
                drafts.append([])
                continue
            drafts.append(ngram_propose(
                seq.all_tokens, d_max, self.cfg.spec_min_ngram,
                self.cfg.spec_max_ngram,
                vocab_size=self.model_cfg.vocab_size))
        return drafts

    def _spec_gate_terms(self, plan: DecodePlan):
        """(n_live, nw, r) for the speculation cost gate."""
        n_live = sum(1 for s in plan.seqs if s is not None)
        nw = next((w for w in reversed(self._window_sizes)
                   if w >= max(1, plan.n_window)), self._window_sizes[0])
        return n_live, nw, self.cfg.spec_dispatch_ratio

    def _spec_bound_ok(self, plan: DecodePlan) -> bool:
        """Cheap precheck before paying the per-slot n-gram scans
        (code-review r5): with the draft total at its upper bound
        (spec_k per live slot) the gate simplifies to
        (1 + ema*spec_k)*(nw + r) > nw*(1 + r); when even that fails,
        no possible draft set passes _spec_worthwhile, so skip the scan
        entirely — unless a forced probe is due (the skip still counts
        toward the probe cadence)."""
        n_live, nw, r = self._spec_gate_terms(plan)
        if n_live == 0:
            return False
        if (1 + self._spec_acc_ema * self.cfg.spec_k) * (nw + r) \
                > nw * (1 + r):
            return True
        self._spec_gate_skips += 1
        # leave the counter at the threshold: _spec_worthwhile's probe
        # branch resets it when the probe actually dispatches
        return self._spec_gate_skips >= self.cfg.spec_probe_every

    def _spec_worthwhile(self, plan: DecodePlan, d_total: int) -> bool:
        """Cost gate (code-review r5): one drafted slot must not pull the
        whole batch off the fused nw-step window. A verify dispatch costs
        ~one decode forward + one host dispatch; the window costs nw
        forwards + one dispatch. With r = dispatch/forward time ratio and
        ema = recent acceptance rate, speculation wins per unit time iff

            (n_live + ema*drafts_total) * (nw + r) > n_live * nw * (1 + r)

        (every live slot still emits >=1 token under verify, so at nw == 1
        speculation is a strict superset and always passes with any
        draft). The ema only updates when verify runs, so every
        spec_probe_every-th rejection forces a probe to re-measure."""
        n_live, nw, r = self._spec_gate_terms(plan)
        if ((n_live + self._spec_acc_ema * d_total) * (nw + r)
                > n_live * nw * (1 + r)):
            self._spec_gate_skips = 0
            return True
        self._spec_gate_skips += 1
        if self._spec_gate_skips >= self.cfg.spec_probe_every:
            self._spec_gate_skips = 0
            return True
        return False

    def _stage_spec(self, plan: DecodePlan, drafts: list, counters,
                    min_toks) -> tuple:
        """The verify block of a speculative step, staged on the device:
        the row for each slot is [last_token, draft...] laid out like a
        prefill chunk (same AttnMetadata conventions as _build_prefill).
        Runs inside the caller's `upload` phase."""
        ps = self.cfg.page_size
        # dynalint: bucketed — a decode plan has one row per slot
        # (max_slots, fixed at construction), live or padding
        s_count = len(plan.seqs)
        kp1 = self.cfg.spec_k + 1
        tokens = np.zeros((s_count, kp1), np.int32)  # dynalint: bucketed
        positions = np.zeros((s_count, kp1), np.int32)  # dynalint: bucketed
        write_idx = np.full((s_count, kp1), -1, np.int32)  # dynalint: bucketed
        kv_lens = np.zeros((s_count,), np.int32)  # dynalint: bucketed
        for i, seq in enumerate(plan.seqs):
            if seq is None:
                continue
            d = drafts[i]
            n = 1 + len(d)
            pos0 = seq.total_len - 1
            tokens[i, 0] = plan.tokens[i, 0]
            if d:
                tokens[i, 1:n] = d
            positions[i, :] = pos0 + n - 1
            positions[i, :n] = np.arange(pos0, pos0 + n)
            for j in range(n):
                write_idx[i, j] = seq.flat_index(pos0 + j, ps)
            kv_lens[i] = pos0 + n
        # VERIFY_OPERANDS' order
        return (("verify", tokens.shape, plan.page_table.shape[1]),
                self._stage_operands(
                    (tokens, positions, plan.page_table, kv_lens, write_idx,
                     counters, min_toks)))

    def _run_spec_decode(self, plan: DecodePlan, drafts: list,
                         block: tuple) -> List[StepOutput]:
        """Verify prompt-lookup drafts in one target forward (engine/spec.py).

        `block` is _stage_spec's staged [S, spec_k+1] verify block; the
        verify program's per-position argmax replays the greedy choice
        at every draft position. Acceptance keeps the longest matching
        prefix and emits the model's own token at the first mismatch, so
        output is token-for-token the plain-greedy output — drafts only
        ever buy speed. Emitted tokens commit through the same
        commit_decode_token + _postprocess path as window tokens (stop /
        eos / max_tokens all enforced there); commitment stops at the
        first finished event, mirroring _commit_window.
        """
        key, args = block
        args = (self.params, self.cache, *args)
        with self._dispatch_phase(key, "verify", self._verify_fn, args):
            pred, self.cache, aux = self._verify_fn(*args)
        with self.phases.phase("wait"):
            pred, aux = jax.device_get((pred, aux))
        self.phases.device_busy = False
        pred = np.asarray(pred)
        if aux:
            self._account_moe(aux)
        with self.phases.phase("commit"):
            return self._commit_spec(plan, drafts, pred)

    def _commit_spec(self, plan: DecodePlan, drafts: list,
                     pred: np.ndarray) -> List[StepOutput]:
        s_count, kp1 = pred.shape
        # verify advanced positions/KV outside the window path: any saved
        # device-resident window state (token/position/counter) is stale
        self._dec_state = None
        events: List[StepOutput] = []
        for i, seq in enumerate(plan.seqs):
            if seq is None:
                continue
            d = drafts[i]
            m = 0
            while m < len(d) and int(pred[i, m]) == d[m]:
                m += 1
            self.spec_proposed_tokens += len(d)
            self.spec_accepted_tokens += m
            if d:
                self._spec_acc_ema = (0.8 * self._spec_acc_ema
                                      + 0.2 * (m / len(d)))
            emitted, finished = 0, False
            for tok in list(d[:m]) + [int(pred[i, m])]:
                self.scheduler.commit_decode_token(seq, tok)
                emitted += 1
                ev = self._postprocess(seq, seq.output[-1])
                events.append(ev)
                if ev.finished:
                    finished = True
                    break
            if self._draft is not None and not finished:
                # draft-cache rows match committed history only through
                # the accepted prefix; record coverage so the next sync
                # replays from the right position. A FINISHED request was
                # already forgotten by _postprocess — re-recording it
                # would leak the entry forever and could poison a reused
                # request id's coverage (code-review r5)
                self._draft.committed(seq, m, emitted)
        self.spec_steps += 1
        # ledger: the verify block charges [S, k+1] bucket tokens; the
        # accepted drafts + the model's own token are the useful part
        self._note_kind(None)
        self._ledger_record(
            "spec", s_count,
            sum(1 for s in plan.seqs if s is not None),
            len(events), s_count * kp1, events=events)
        return events

    def _commit_window(self, plan: DecodePlan, toks: np.ndarray, lps=None,
                       top_ids=None, top_lps=None,
                       reconciled: bool = False, ahead: bool = False,
                       opened: Optional[dict] = None) -> List[StepOutput]:
        """Unpack a [N, S] window of sampled tokens step-major so each
        request's tokens stream in generation order; stop accounting a
        sequence at its first finished token (later window tokens for it
        are garbage by construction). `reconciled`: a follow-up window
        that a commit ended a row under; it is committed for the rows
        still live like any other, and counted. `ahead`: the window was
        dispatched before the step in front of it was fetched
        (_note_kind). `opened`: the commit was opened before the fetch
        (_open_window) and this is the rest of it: the rows are the ones
        live then, and each token takes the place held for it
        (_place_token)."""
        n_steps = toks.shape[0]
        self.step_count += n_steps - 1             # window counts as N steps
        events: List[StepOutput] = []
        done: Set[str] = set()
        finish_step: Dict[str, int] = {}
        # identity guard for the pipelined loop: a slot whose sequence
        # ended or was aborted while this window was in flight is no
        # longer backed by this seq — committing its tokens would
        # double-free pages (or poison a reused request id); the
        # synchronous path commits immediately after scheduling, so the
        # guard is vacuous there
        live = self._live_rows(plan) if opened is None else opened["live"]
        n_live = sum(live)
        if not n_live:
            # every row left while the window ran: its rung reached no one
            self.window_steps_discarded += n_steps
        else:
            self._note_kind("decode", ahead)
            if reconciled:
                self.window_steps_reconciled += n_steps
        for step in range(n_steps):
            for i, seq in enumerate(plan.seqs):
                if not live[i] or seq.request_id in done:
                    continue
                if opened is not None:
                    ev = self._place_token(opened["at"][i], step,
                                           int(toks[step, i]))
                    events.append(ev)
                    if ev.finished:
                        done.add(seq.request_id)
                        finish_step[seq.request_id] = step
                    continue
                self.scheduler.commit_decode_token(seq, int(toks[step, i]))
                if lps is not None:
                    ev = self._postprocess(seq, seq.output[-1],
                                           float(lps[step, i]),
                                           top_ids[step, i],
                                           top_lps[step, i])
                else:
                    ev = self._postprocess(seq, seq.output[-1])
                events.append(ev)
                if ev.finished:
                    done.add(seq.request_id)
                    finish_step[seq.request_id] = step
        # wasted-step accounting (VERDICT r3 weak #3): device steps a slot
        # ran after its request finished inside this window. The device
        # `alive` mask keeps these from writing KV/burning MoE capacity;
        # the counter sizes the remaining tail-compute waste for window
        # tuning (exported via metrics()).
        self.window_slot_steps += n_steps * n_live
        self.window_wasted_steps += sum(n_steps - 1 - s
                                        for s in finish_step.values())
        # ledger sample for the committed window: the bucket charge is
        # every (step, slot) pair of the window; useful = tokens that
        # actually committed (post-finish tail + padding rows = waste)
        self._ledger_record("decode", len(plan.seqs), n_live,
                            len(events), n_steps * len(plan.seqs),
                            dev_steps=n_steps if n_live else 0,
                            events=events)
        return events

    def _run_decode_pp(self, plan: DecodePlan) -> List[StepOutput]:
        """Pipeline-parallel decode. Greedy AND sampled plans run
        multi-token windows: slot-group microbatches round-robin through
        the pipeline so other slots' steps fill the bubble between one
        slot's consecutive tokens, and the sampling state (temperature /
        top-k / top-p / per-slot seed+counter keys) runs on the last
        stage through the shared sample_logits tail
        (models/pp.pp_decode_window; VERDICT r3 weak #7 + r4 #6).
        Logprob / penalty plans take one token per dispatch through the
        same fused program prefill uses."""
        with self.phases.phase("upload"):
            samp = self._sampling_arrays(plan.seqs)
            greedy = self._samp_cache.all_greedy
            with_lp = self._wants_logprobs(plan.seqs)
            rp = self._rep_penalty_arrays(plan.seqs)
            drafts = self._spec_drafts(plan, greedy, with_lp, rp)
            staged = step = None
            if drafts is not None:
                block = self._stage_spec(plan, drafts, samp[4], samp[5])
            elif plan.n_window > 1 and not with_lp and rp is None:
                staged = self._stage_pp_window(plan, samp, greedy)
            else:
                step = self._stage_step(plan, plan.seqs)
        if drafts is not None:
            return self._run_spec_decode(plan, drafts, block)
        if staged is not None:
            outs, nxt = self._dispatch_staged(staged, staged["first"])
            self._dec_state = {"sig": staged["sig"], "dev": staged["dev"],
                               "next": nxt}
            return self._fetch_and_commit(plan, outs)
        sampled = self._launch_step(step)
        with self.phases.phase("commit"):
            return self._commit_pp_tokens(plan, sampled)

    def _commit_pp_tokens(self, plan: DecodePlan, sampled
                          ) -> List[StepOutput]:
        lps = self._last_logprobs
        events: List[StepOutput] = []
        for i, seq in enumerate(plan.seqs):
            if seq is None:
                continue
            self.scheduler.commit_decode_token(seq, int(sampled[i]))
            if lps is not None:
                events.append(self._postprocess(
                    seq, seq.output[-1], float(lps[0][i]), lps[1][i],
                    lps[2][i]))
            else:
                events.append(self._postprocess(seq, seq.output[-1]))
        self._note_kind("decode")
        self._ledger_record("decode", len(plan.seqs), len(events),
                            len(events), len(plan.seqs), events=events)
        return events

    def _postprocess(self, seq: SequenceState, tok: int,
                     lp: Optional[float] = None, top_ids=None,
                     top_lps=None, opened: Optional[tuple] = None
                     ) -> StepOutput:
        """`opened`: (params, ended, tokens out) of a row whose commit
        was opened before `tok` was known (_open_mixed, _open_window):
        the params it had then, whether the caller carries out its end
        itself, and its output's length with `tok` its last."""
        p, ended, n_out = opened or (
            self.scheduler.params[seq.request_id], False, len(seq.output))
        finish = None
        emit: Optional[int] = tok
        # Hidden stop ids always stop and are never emitted. EOS before
        # min_tokens cannot occur: the device step masks eos logits while
        # the emitted count is below min_tokens.
        if tok in p.stop_token_ids:
            finish, emit = "stop", None
        elif not p.ignore_eos and tok in self.eos_token_ids:
            finish, emit = "stop", None
        elif n_out >= p.max_tokens:
            finish = "length"
        if finish is not None and not ended:
            self.scheduler.finish(seq)
            if self._draft is not None:
                self._draft.forget(seq.request_id)
        ev = StepOutput(seq.request_id, emit, finish is not None, finish)
        if p.logprobs is not None and emit is not None and lp is not None:
            ev.logprob = lp
            k = max(0, min(int(p.logprobs), len(top_ids)))
            ev.top_logprobs = [(int(t), float(v))
                               for t, v in zip(top_ids[:k], top_lps[:k])]
        return ev

    # -- host KV tier --------------------------------------------------------

    def _offload_page(self, pid: int, seq_hash: int) -> None:
        """Allocator eviction hook: queue the page for a batched HBM -> host
        copy (reference: CopyStream offload role). The extract is deferred to
        the next cache-writing operation (_process_offloads), which runs
        before anything can overwrite the evicted page's content."""
        self._pending_offloads.append((pid, seq_hash))

    def _process_offloads(self) -> None:
        """Batched extract of all pages evicted since the last device-cache
        write. The extraction is *dispatched* here — before anything can
        overwrite the evicted pages, preserving device-order correctness —
        but the blocking device→host copy + host put run on the CopyStream
        thread, so the step loop never stalls on an offload."""
        pending, self._pending_offloads = self._pending_offloads, []
        if self._copy_stream is None:  # closed engine: offloads become no-ops
            return
        max_b = self.scheduler.page_buckets[-1]
        for start in range(0, len(pending), max_b):
            chunk = pending[start:start + max_b]
            pages = self.extract_pages([pid for pid, _ in chunk])
            self._copy_stream.submit(pages, [h for _, h in chunk])

    def _process_onboards(self) -> None:
        """Inject host-tier pages claimed by _match_prefix into HBM before
        the device step that reads them."""
        pending = self.scheduler.drain_onboards()
        max_b = self.scheduler.page_buckets[-1]
        for start in range(0, len(pending), max_b):
            chunk = pending[start:start + max_b]
            ids = [pid for pid, _ in chunk]
            got = [self.host_pool.get(h) for _, h in chunk]
            nb = next_bucket(len(ids), self.scheduler.page_buckets)
            # [L, Hkv, Nb, ps(, hd)] per leaf; unused tail pages stay
            # zero + dropped. kv_quant tiers return 4 leaves (int8 pages
            # + f32 scale rows) — stacked and injected as-is, never
            # dequantized on the onboard path.
            n_leaves = len(got[0])
            stacks = []
            for leaf in range(n_leaves):
                first = got[0][leaf]
                arr = np.zeros(first.shape[:2] + (nb,) + first.shape[2:],
                               first.dtype)
                for i, page in enumerate(got):
                    arr[:, :, i] = page[leaf]
                stacks.append(arr)
            # unpin only AFTER copying out of the slab views: put() (on the
            # CopyStream thread) never evicts pinned slots, so the views
            # above were stable until here
            for _, h in chunk:
                self.host_pool.unpin(h)
            shd = self.cache_sharding
            k_dev = jax.device_put(jnp.asarray(stacks[0]), shd)
            v_dev = jax.device_put(jnp.asarray(stacks[1]), shd)
            if n_leaves == 4:
                sshd = self.cache_scale_sharding
                self.inject_pages(
                    ids, k_dev, v_dev,
                    jax.device_put(jnp.asarray(stacks[2]), sshd),
                    jax.device_put(jnp.asarray(stacks[3]), sshd))
            else:
                self.inject_pages(ids, k_dev, v_dev)
            self.host_pool.stats.onboarded += len(ids)

    # -- disaggregation ------------------------------------------------------

    def allocate_remote(self, req: EngineRequest):
        """Decode side: allocate pages up-front for a remote prefill."""
        if self.cfg.sp > 1:
            # an sp engine's prefill path is ring attention over the whole
            # prompt; remote activation would re-enter scheduling with a
            # mid-sequence chunk the ring path must not see. SP engines are
            # the prefill side of disaggregation, not the decode side.
            return None
        refuse_unserved(
            self.model_cfg, feature="disagg transfer (a remote prefill "
            "leaves K and V pages of one pool, and no state)")
        # per-hash copy settling happens inside the prefix walk, as in
        # add_request (this path also matches against the host tier)
        return self.scheduler.add_remote(
            self._validate_prompt(self._resolve_mm(req)))

    def activate_remote(self, request_id: str, first_token: int) -> None:
        self.scheduler.activate_remote(request_id, first_token)

    def preactivate_remote(self, request_id: str, first_token: int,
                           needed_pages: int, frontier_fn) -> None:
        """Decode side, early-decode overlap: arm a committed-frontier
        gate so the sequence activates the moment every transferred
        page is verified + injected, instead of waiting for stream
        completion + the notify round trip (docs/PERF.md).
        `frontier_fn` must answer the MIN over per-stream frontiers on
        sharded parallel transfers (the transfer server's aggregation)
        — the gate may only open once every shard slice landed."""
        self.scheduler.preactivate_remote(request_id, first_token,
                                          needed_pages, frontier_fn)

    def cancel_overlap(self, request_id: str) -> bool:
        return self.scheduler.cancel_overlap(request_id)

    def release_remote(self, request_id: str) -> None:
        self.scheduler.release_remote(request_id)

    def salvage_remote(self, request_id: str, valid_pages: int,
                       first_token=None) -> int:
        """Decode side: the remote prefill is unrecoverable but the
        streamed transfer COMMITTED a prefix (verified + injected +
        acked chunks). Keep those pages and re-prefill locally only
        from the committed page boundary — the disagg twin of the
        migration path's committed-prefix re-dispatch. `valid_pages`
        must come from the MIN-over-streams frontier aggregation on
        sharded parallel transfers: a page is only salvageable when
        EVERY shard stream committed its slice. `first_token` seeds
        the already-emitted first output token on the early-decode
        overlap path. Returns the salvaged token count."""
        return self.scheduler.salvage_remote(request_id, valid_pages,
                                             first_token=first_token)

    def release_parked(self, request_id: str) -> None:
        self.scheduler.release_parked(request_id)

    def _bucket_ids(self, page_ids) -> np.ndarray:
        """Pad a page-id list to a bucketed static shape; padding ids point
        past the cache so extract reads garbage that inject later drops."""
        n = max(len(page_ids), 1)
        nb = next_bucket(n, self.scheduler.page_buckets)
        out = np.full((nb,), self.cfg.num_pages, np.int32)
        out[:len(page_ids)] = page_ids
        return out

    def extract_pages(self, page_ids) -> dict:
        """Gather whole KV pages -> ({k,v[,k_scale,v_scale]}, on-device):
        values [L, Hkv, Nb, ps, hd] plus scale stacks [L, Hkv, Nb, ps] on
        kv_quant engines — the stored representation, never dequantized,
        a head a row (a pool of `kv_row_heads` > 1 re-views the pages it
        gathered: _logical_pages)."""
        refuse_unserved(
            self.model_cfg, feature="whole-page extraction (disagg "
            "transfer, the shared KV pool)")
        ids = jnp.asarray(self._bucket_ids(page_ids))
        ids = jnp.minimum(ids, self.cfg.num_pages - 1)  # clamp padding reads
        return self._extract_fn(self.cache, ids)

    def inject_pages(self, page_ids, k_pages, v_pages,
                     k_scale=None, v_scale=None) -> None:
        """Scatter whole KV pages into this engine's cache (donated update).

        The caller is responsible for placing k/v on this engine's mesh with
        cache sharding (transfer.py does the cross-mesh device_put — the
        ICI/DCN reshard that replaces the reference's kv_rearrange kernel).

        kv_quant engines require the matching scale stacks: pages travel
        in the quantized representation end-to-end, and a peer that sends
        bf16 pages into an int8 cache (or vice versa) is a deployment
        error, named rather than silently cast.

        The id padding follows the SENDER's bucket (k_pages.shape[2]), not
        ours — the two engines may have different max_model_len and hence
        different page-count buckets; padding ids drop on scatter."""
        refuse_unserved(
            self.model_cfg, feature="whole-page injection (disagg "
            "transfer, the shared KV pool)")
        if self.kv_quant and k_scale is None:
            raise ValueError(
                "this engine stores int8 KV pages (kv_quant="
                f"{self.kv_quant!r}) but the sender shipped no scales; "
                "both sides of a transfer must run the same kv_quant mode")
        if not self.kv_quant and k_scale is not None:
            raise ValueError(
                "sender shipped quantized KV pages but this engine's "
                "cache is unquantized; both sides of a transfer must run "
                "the same kv_quant mode")
        # evicted-but-unsaved pages must reach the host slab before this
        # write can overwrite them (disagg injects land on evicted pages)
        if self._pending_offloads:
            self._process_offloads()
        nb = k_pages.shape[2]
        if len(page_ids) > nb:
            raise ValueError(
                f"{len(page_ids)} dst pages but only {nb} pages sent")
        ids = np.full((nb,), self.cfg.num_pages, np.int32)
        ids[:len(page_ids)] = page_ids
        pages = {"k": k_pages, "v": v_pages}
        if k_scale is not None:
            pages["k_scale"] = k_scale
            pages["v_scale"] = v_scale
        self.cache = self._inject_fn(self.cache, jnp.asarray(ids), pages)

    def shard_slices(self, n_streams: int = 0) -> list:
        """This engine's KV transfer shard plan: one slice tuple per
        parallel transfer stream (parallel/mesh.kv_shard_layout over the
        mesh's tp/pp extents — the cache sharding spec's shard blocks).
        The disagg data plane opens one chunk-committed stream per
        (slice, destination host) and the receiver injects each slice
        independently; `n_streams` overrides the natural shard count on
        non-pp meshes (must divide num_kv_heads)."""
        from dynamo_tpu.parallel.mesh import kv_shard_layout
        return kv_shard_layout(self.model_cfg.num_layers,
                               self.model_cfg.num_kv_heads,
                               tp=self.mesh.shape.get("tp", 1),
                               pp=self.pp, n_streams=n_streams)

    def inject_pages_shard(self, page_ids, k_pages, v_pages, slices,
                           k_scale=None, v_scale=None) -> None:
        """Scatter a SHARD SLICE of whole KV pages into this engine's
        cache: the sharded-parallel-transfer twin of inject_pages.

        `slices` is one entry of shard_slices() — ((axis, start, count),
        ...) over the leading (layer, kv-head) axes, shared by the value
        leaves ([Ls, Hs, Nb, ps, hd]) and the kv_quant scale leaves
        ([Ls, Hs, Nb, ps]). Each stream's chunks land here independently
        of its sibling streams; a page is only USABLE once every stream
        covering it has committed — the min-over-streams frontier the
        transfer server aggregates (KvTransferServer.committed_frontier)
        gates decode, so a partially-assembled page is never read.

        The update compiles once per (plan entry, id bucket): the slice
        bounds are static, only page ids are data."""
        if self.kv_quant and k_scale is None:
            raise ValueError(
                "this engine stores int8 KV pages (kv_quant="
                f"{self.kv_quant!r}) but the sender shipped no scales; "
                "both sides of a transfer must run the same kv_quant mode")
        if not self.kv_quant and k_scale is not None:
            raise ValueError(
                "sender shipped quantized KV pages but this engine's "
                "cache is unquantized; both sides of a transfer must run "
                "the same kv_quant mode")
        if self._pending_offloads:
            self._process_offloads()
        nb = k_pages.shape[2]
        if len(page_ids) > nb:
            raise ValueError(
                f"{len(page_ids)} dst pages but only {nb} pages sent")
        ids = np.full((nb,), self.cfg.num_pages, np.int32)
        ids[:len(page_ids)] = page_ids
        pages = {"k": k_pages, "v": v_pages}
        if k_scale is not None:
            pages["k_scale"] = k_scale
            pages["v_scale"] = v_scale
        key = tuple(tuple(s) for s in slices)
        fn = self._inject_shard_fns.get(key)
        if fn is None:
            fn = self._inject_shard_fns[key] = jax.jit(
                functools.partial(_inject_pages_slice, slices=key,
                                  row_heads=self.model_cfg.kv_row_heads,
                                  pad=self.model_cfg.kv_row_pad),
                donate_argnums=(0,))
        self.cache = fn(self.cache, jnp.asarray(ids), pages)

    # -- introspection -------------------------------------------------------

    def metrics(self):
        m = self.scheduler.metrics()
        m.window_slot_steps = self.window_slot_steps
        m.window_wasted_steps = self.window_wasted_steps
        m.spec_proposed_tokens = self.spec_proposed_tokens
        m.spec_accepted_tokens = self.spec_accepted_tokens
        m.decode_windows = self.decode_windows
        m.decode_dispatches = self.decode_dispatches
        m.pipeline_windows = self.pipeline_windows
        m.pipeline_overlapped = self.pipeline_overlapped
        m.pipeline_fallbacks = self.pipeline_fallbacks
        m.window_steps_reconciled = self.window_steps_reconciled
        m.window_steps_discarded = self.window_steps_discarded
        m.decode_host_syncs = self.decode_host_syncs
        m.decode_plan_uploads = self.decode_plan_uploads
        m.host_buffers = self.host_buffers
        m.mixed_steps = self.mixed_steps
        m.mixed_steps_chained = self.mixed_steps_chained
        m.mixed_steps_replanned = self.mixed_steps_replanned
        m.handovers = self.handovers
        m.handovers_chained = self.handovers_chained
        m.decode_stall_steps = self.decode_stall_steps
        # KV representation gauges (ops/kv_quant.py): bytes one page
        # occupies in HBM (k+v+scales) and the quant mode's bit width
        # (0 = unquantized); transfer volume comes from the process-
        # global counters so prefill-side sends surface on the sender's
        # own metrics (refreshed per metrics() call, like the PR-4
        # robustness gauges)
        from dynamo_tpu.ops.kv_quant import leaf_page_bytes
        from dynamo_tpu.runtime.integrity import XFER_STATS
        mc, ec = self.model_cfg, self.cfg
        m.kv_page_bytes = sum(
            leaf_page_bytes(mc.num_cache_layers, heads, ec.page_size, width,
                            jnp.dtype(mc.dtype).itemsize,
                            bool(self.kv_quant))
            for heads, width in mc.kv_cache_leaves().values())
        m.kv_quant_bits = 8 if self.kv_quant == "int8" else 0
        m.kv_transfer_bytes = XFER_STATS.bytes_sent
        m.kv_transfer_fetches = XFER_STATS.fetches
        m.kv_transfer_resumes = XFER_STATS.resumes
        m.kv_transfer_salvaged_pages = XFER_STATS.salvaged_pages
        m.kv_transfer_stale_chunks = XFER_STATS.stale_chunks
        m.kv_transfer_link_timeouts = XFER_STATS.link_timeouts
        # per-step ledger figures (observability/ledger.py), per-engine:
        # steps/recompiles/padding waste are this instance's cumulative
        # counters; tok_s is the EWMA instantaneous committed rate; the
        # offload tier occupancy mirrors the ledger's per-tier sample
        m.engine_steps = self.ledger.steps
        m.engine_recompiles = self.ledger.recompiles_total
        m.engine_tok_s = round(self.ledger.tok_s, 3)
        m.engine_pad_frac = round(self.ledger.pad_fraction(), 4)
        # the two exposures and the stalls (ledger.close_call)
        for name in ("host_exposed_handover_seconds",
                     "host_exposed_drain_seconds", "period_stalls_total",
                     "period_stall_seconds", "period_stall_wait_seconds"):
            setattr(m, name, getattr(self.ledger.stats, name))
        if self.host_pool is not None:
            m.kv_host_pages_used = self.host_pool.used
            m.kv_host_pages_total = self.host_pool.capacity
            if self.host_pool.disk is not None:
                m.kv_disk_pages_used = self.host_pool.disk.used
                m.kv_disk_pages_total = self.host_pool.disk.capacity
        if self._streamer is not None:
            from dynamo_tpu.engine.streaming import STREAM_STATS
            m.kv_stream_steps = int(STREAM_STATS.stream_steps)
            m.kv_stream_prefetch_hit = int(STREAM_STATS.prefetch_hit)
            m.kv_stream_prefetch_late = int(STREAM_STATS.prefetch_late)
            m.kv_stream_pages_spilled = int(STREAM_STATS.pages_spilled)
            m.kv_stream_pages_quarantined = int(
                STREAM_STATS.pages_quarantined)
            m.kv_stream_stall_steps = int(STREAM_STATS.stall_steps)
        return m

    def moe_drop_rate(self) -> float:
        """Fraction of routed (token, expert) assignments dropped over
        expert capacity since engine start (0.0 for non-MoE models)."""
        if self.moe_routed_tokens <= 0:
            return 0.0
        return self.moe_dropped_tokens / self.moe_routed_tokens

    def drain_kv_events(self):
        events = self.scheduler.allocator.drain_events()
        if self._pool_stream is not None and events:
            self._publish_pool_pages(events)
        return events

    # -- cluster-wide shared KV pool (engine/kv_pool.py) ---------------------

    def attach_kv_pool(self, pool, source_id: str,
                       publish: bool = True) -> None:
        """Join the cluster KV namespace: the prefix walk gains the
        content-addressed pool tier below host/disk, and (publish=True)
        every sealed full page this engine commits is published into the
        pool off the step loop. `source_id` is this worker's id — pool
        events ride the KV-event plane under `pool:{source_id}` so the
        router learns pool-resident prefixes (kv_router/protocols.py)."""
        from dynamo_tpu.engine.kv_pool import PoolPublishStream
        refuse_unserved(self.model_cfg, feature="the shared KV pool")
        self.kv_pool = pool
        self.kv_pool_source = source_id
        self.scheduler.kv_pool = pool
        self.scheduler.kv_pool_mode = self.kv_quant
        if publish:
            self._pool_stream = PoolPublishStream(pool, source_id,
                                                  mode=self.kv_quant)

    def _publish_pool_pages(self, events) -> None:
        """Tee newly-sealed full pages into the shared pool.

        Runs at event-drain time, right after the step that sealed them —
        the pages' contents are still intact (nothing writes the cache
        between a step and the next), so the extraction dispatched here
        captures the authoritative bytes; the PoolPublishStream thread
        does the blocking D2H, computes the capture checksum the pool
        verifies on every later fetch, and publishes. Hashes already
        pool-resident skip the D2H (`note_source` — their one stored
        copy was checksum-verified at its own publish)."""
        ship_ids, ship_metas = [], []
        for kind, pid, sh, parent, th in events:
            if kind != "stored":
                continue
            if sh in self.kv_pool:
                self.kv_pool.note_source(self.kv_pool_source, sh,
                                         parent, th)
            else:
                ship_ids.append(pid)
                ship_metas.append((sh, parent, th))
        max_b = self.scheduler.page_buckets[-1]
        for start in range(0, len(ship_ids), max_b):
            pages = self.extract_pages(ship_ids[start:start + max_b])
            self._pool_stream.submit(pages,
                                     ship_metas[start:start + max_b])

    def _process_pool_injects(self) -> None:
        """Inject shared-pool pages claimed by _match_prefix into HBM
        before the device step that reads them. The bytes arrived
        checksum-verified from the claim (scheduler._pool_claim ->
        SharedKvPool.fetch: verify against the traveling capture
        checksum, quarantine on mismatch), so this is pure transport —
        the tier twin of _process_onboards."""
        pending = self.scheduler.drain_pool_injects()
        # recycling fence: a claim whose sequence was released before
        # this drain may have had its page freed and REALLOCATED — only
        # inject into pages still carrying the claimed seal (a freed-
        # but-unrecycled reusable page keeps its hash and the inject is
        # still the content that hash names)
        alloc = self.scheduler.allocator
        pending = [(pid, arrays) for pid, h, arrays in pending
                   if alloc.pages[pid].seq_hash == h]
        if not pending:
            return
        max_b = self.scheduler.page_buckets[-1]
        for start in range(0, len(pending), max_b):
            chunk = pending[start:start + max_b]
            ids = [pid for pid, _ in chunk]
            got = [arrays for _, arrays in chunk]
            nb = next_bucket(len(ids), self.scheduler.page_buckets)
            n_leaves = len(got[0])
            stacks = []
            for leaf in range(n_leaves):
                first = got[0][leaf]
                arr = np.zeros(first.shape[:2] + (nb,) + first.shape[2:],
                               first.dtype)
                for i, page in enumerate(got):
                    arr[:, :, i] = page[leaf]
                stacks.append(arr)
            shd = self.cache_sharding
            k_dev = jax.device_put(jnp.asarray(stacks[0]), shd)
            v_dev = jax.device_put(jnp.asarray(stacks[1]), shd)
            if n_leaves == 4:
                sshd = self.cache_scale_sharding
                self.inject_pages(
                    ids, k_dev, v_dev,
                    jax.device_put(jnp.asarray(stacks[2]), sshd),
                    jax.device_put(jnp.asarray(stacks[3]), sshd))
            else:
                self.inject_pages(ids, k_dev, v_dev)

    def prefetch_pool_pages(self, tokens) -> int:
        """PRESERVE-style admission-window warm-up: fetch this prompt's
        leading pool-resident pages into HBM NOW, sealed into the
        allocator's REUSABLE pool (ref_count 0, keyed by chained hash),
        so a later admission's prefix walk hits device memory.

        Every fetch is checksum-verified at claim (_pool_claim); a
        failure mid-chain keeps the pages already warmed and stops.
        Warmed pages are ordinary evictable prefix-cache entries tied to
        no request — a prefetch racing an admission cancel or deadline
        leaves no leaked HBM pages, and double-prefetching is a no-op
        (the allocator lookup short-circuits). Runs between device steps
        (worker.submit); returns pages warmed."""
        sch = self.scheduler
        if sch.kv_pool is None or self.cfg.sp > 1:
            return 0
        from dynamo_tpu.engine.kv_cache import page_hash
        from dynamo_tpu.engine.kv_pool import POOL_STATS
        ps = self.cfg.page_size
        parent, warmed, pids = 0, 0, []
        for i in range(len(tokens) // ps):
            toks = list(tokens[i * ps:(i + 1) * ps])
            h = page_hash(parent, toks)
            if sch.allocator.lookup(h) is not None \
                    or (sch.host_pool is not None and h in sch.host_pool):
                parent = h
                continue   # already warm in a local tier
            if h not in sch.kv_pool or not sch.allocator.can_allocate(1):
                break
            got = sch._pool_claim(h)
            if got is None:
                break
            pid = sch.allocator.allocate()
            sch.allocator.seal(pid, parent, toks)
            sch.pending_pool_injects.append((pid, h, got))
            pids.append(pid)
            warmed += 1
            parent = h
        if warmed:
            self._process_pool_injects()
            for pid in pids:
                # release into the reuse pool: content + hash stay until
                # LRU eviction, exactly like a finished request's pages
                sch.allocator.free(pid)
            POOL_STATS.prefetch_pages += warmed
        return warmed


def _abstract(x):
    """An argument of a program as its shape: an array's shape, dtype
    and, where it is committed to one, its sharding (what `jit` keys a
    lowering on); anything else (the static operand layout) as it is."""
    if not isinstance(x, jax.Array):
        return x
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding if x.committed else None)


def _carry_behind(prev_tokens, carry):
    """The [S, 3] carry of a window dispatched behind a step in flight:
    `carry` [S, 4] is the host's (token, position, counter, src); a row
    with `src >= 0` takes its token from `prev_tokens[src]`, the tokens of
    the step in front of it, which never left the device."""
    src = carry[:, 3]
    tok = jnp.where(src >= 0, prev_tokens[jnp.maximum(src, 0)], carry[:, 0])
    return carry[:, :3].at[:, 0].set(tok)


def _tokens_behind(cap: int, carry):
    """A window's last tokens (its [S, 3] carry out) at the one length
    `cap` an `_engine_step` takes the tokens of the step before it in:
    row i is slot i's."""
    return jnp.full((cap,), -1, jnp.int32).at[:carry.shape[0]].set(
        carry[:, 0])


def pack_operands(arrays) -> tuple:
    """A step's small host operands as ONE int32 buffer: (layout, buf).

    `arrays` are NumPy arrays of int32, float32 or bool that share their
    leading (row) axis, each `[rows]` or `[rows, w]`. `buf` is
    `[rows, sum of widths]` int32 with the operands side by side in the
    order given: float32 columns carry their bits, bool columns 0/1.
    `layout` is one `(dtype char, w)` per operand (`w` None for a `[rows]`
    operand; 0 is a `[rows, 0]` one, a window without stop ids): a pure
    function of the operands' shapes and dtypes, which are already in
    every program's key, so it rides the jitted call as a static argument
    and `unpack_operands` takes the buffer apart again by static slices."""
    layout = tuple((a.dtype.char, a.shape[1] if a.ndim == 2 else None)
                   for a in arrays)
    buf = np.empty((arrays[0].shape[0],
                    sum(1 if w is None else w for _, w in layout)), np.int32)
    off = 0
    for a, (kind, w) in zip(arrays, layout):
        if kind == "f":
            a = np.ascontiguousarray(a).view(np.int32)
        elif kind not in "i?" or a.ndim > 2:
            raise TypeError(f"operand {a.dtype}{a.shape} does not pack: "
                            "int32, float32 or bool, [rows] or [rows, w]")
        if w is None:
            buf[:, off] = a
            off += 1
        else:
            buf[:, off:off + w] = a
            off += w
    return layout, buf


def unpack_operands(layout: tuple, buf) -> tuple:
    """Inside a program: the operands `pack_operands` laid side by side,
    each with the shape, dtype and bits it had on the host."""
    out, off = [], 0
    for kind, w in layout:
        col = buf[:, off] if w is None else buf[:, off:off + w]
        if kind == "f":
            col = jax.lax.bitcast_convert_type(col, jnp.float32)
        elif kind == "?":
            col = col != 0
        out.append(col)
        off += 1 if w is None else w
    return tuple(out)


# operand names of each program, in the order its staging site packs them
# (_stage_step, _stage_window, _stage_pp_window, _stage_spec); a variant
# appends its own ("rep_penalty", "mm_mask") where the program is built
STEP_OPERANDS = ("tokens", "positions", "page_table", "kv_lens", "write_idx",
                 "last_idx", "temperature", "top_k", "top_p", "seeds",
                 "counters", "min_tokens", "src")
WINDOW_OPERANDS = ("page_table", "base_table", "max_pos", "temperature",
                   "top_k", "top_p", "seeds", "min_tokens", "ignore_eos",
                   "stop_ids")
PP_WINDOW_OPERANDS = ("page_table", "max_pos", "min_tokens", "ignore_eos",
                      "stop_ids", "temperature", "top_k", "top_p", "seeds")
VERIFY_OPERANDS = ("tokens", "positions", "page_table", "kv_lens",
                   "write_idx", "counters", "min_tokens")


def _packed(fn, names: tuple, own: tuple = (), carried: bool = False,
            fed: bool = False):
    """`fn` as the step path calls it (NativeEngine._stage_operands):
    `program(params, cache, [carry,] layout, packed, *own)`. The operands
    `names` arrive side by side in ONE buffer and are taken apart by the
    static `layout` (unpack_operands): the same values, dtypes and shapes
    reach the same ops of `fn`, which is unchanged. `own` names the
    operands that keep a buffer to themselves. A `carried` program (a
    decode window) takes its (token, position, counter) as one [S, 3]
    array and hands on the next in the same form, so a chained window is
    fed the device's own. A `fed` program (`_engine_step`) takes the
    tokens of the step before it in the same place, a device array too."""
    @jax.named_scope("step")
    def program(params, cache, *args):
        kw = {}
        if fed:
            kw["prev_tokens"], *args = args
        with jax.named_scope("step.unpack"):
            if carried:
                carry, *args = args
                kw.update(tokens=carry[:, 0], positions=carry[:, 1],
                          counters=carry[:, 2])
            layout, buf, *rest = args
            kw.update(zip(names, unpack_operands(layout, buf),
                          strict=True))
        kw.update(zip(own, rest, strict=True))
        out = fn(params, cache, **kw)
        if carried:
            out = (*out[:-1], jnp.stack(out[-1], axis=1))
        return out
    return program


def _named(name: str, fn):
    """`fn` under a stable `__name__`: jax names a jitted program's XLA
    module `jit_<__name__>`, and a `functools.partial` has none (every
    engine program was `jit__unknown` in a device trace). Positional and
    keyword arguments pass through, so `donate_argnums` still counts."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program


def _logical_pages(pages, f: int, pad: int = 0):
    """Pages of a pool whose rows hold f KV heads [L, Hkv / f, Nb, ps,
    f * hd] -> [L, Hkv, Nb, ps, hd], the form a page has outside the
    device pool (the wire, the offload tiers, the shared pool, their
    checksums): one transpose of the handful of pages moved. A row stored
    with `pad` zero lanes past the model's values (a latent row in whole
    lane tiles, engine/config.kv_row_lanes) leaves without them."""
    l, rows, nb, ps, width = pages.shape
    hd = (width - pad) // f
    return pages[..., :f * hd].reshape(l, rows, nb, ps, f, hd).transpose(
        0, 1, 4, 2, 3, 5).reshape(l, rows * f, nb, ps, hd)


def _stored_pages(pages, f: int, pad: int = 0):
    """The way back: [L, Hkv, Nb, ps, hd] -> [L, Hkv / f, Nb, ps, f * hd
    + pad], the pad lanes ZEROS whatever the slot held."""
    l, hkv, nb, ps, hd = pages.shape
    rows = pages.reshape(l, hkv // f, f, nb, ps, hd).transpose(
        0, 1, 3, 4, 2, 5).reshape(l, hkv // f, nb, ps, f * hd)
    return jnp.pad(rows, [(0, 0)] * 4 + [(0, pad)]) if pad else rows


def _extract_pages(cache, ids, row_heads: int = 1, pad: int = 0):
    """Gather pages by ids [Nb] along the page axis (2) of EVERY cache
    leaf -> values [L, Hkv, Nb, ps, hd] and, on kv_quant engines, the
    scale stacks [L, Hkv, Nb, ps], which move with the same ids. A pool
    of `row_heads` > 1 heads a row, or of rows `pad` lanes wider than
    the model's (neither ever quantized), hands its pages out a head a
    row at the model's width, as every other pool does
    (_logical_pages)."""
    # dynalint: kv-codec — whole-page moves keep the stored (possibly
    # quantized) representation; no value decode happens here
    pages = {key: jnp.take(arr, ids, axis=2) for key, arr in cache.items()}
    if row_heads > 1 or pad:
        pages = {key: _logical_pages(arr, row_heads, pad)
                 for key, arr in pages.items()}
    return pages


def _inject_pages(cache, ids, pages, row_heads: int = 1, pad: int = 0):
    """Scatter pages into the cache at ids; out-of-range ids are dropped.
    `pages` carries the same leaf set as the cache (values + scales on
    kv_quant engines), a head a row at the model's width; a pool of
    `row_heads` > 1 or of padded rows takes them re-viewed to its rows
    (_stored_pages)."""
    # dynalint: kv-codec — whole-page moves of the stored representation
    if row_heads > 1 or pad:
        pages = {key: _stored_pages(pages[key], row_heads, pad)
                 for key in cache}
    return {key: cache[key].at[:, :, ids].set(pages[key], mode="drop")
            for key in cache}


def _inject_pages_slice(cache, ids, pages, slices=(), row_heads: int = 1,
                        pad: int = 0):
    """Scatter a shard slice of pages into the cache at ids: `slices`
    ((axis, start, count), ...) are STATIC bounds over the leading
    (layer, kv-head) axes — one compiled program per shard-plan entry.
    Out-of-range ids drop, exactly like _inject_pages. ONE mixed
    basic+advanced `.at[]` per leaf (static slices + the page-id array,
    which numpy semantics keep in place as the single advanced index):
    a direct strided scatter on the donated buffer, never a
    materialized sub-cache copy — the per-chunk inject cost is O(chunk
    slice), not O(cache). A pool of `row_heads` f > 1 heads a row takes a
    slice of KV heads (which a sender's plan may cut anywhere) as one
    such scatter a lane group: head h lands in row h // f, lanes
    (h % f) * hd .., so the heads of the slice that share a lane group
    are every f-th, and a run of rows. A pool of rows `pad` lanes wider
    than the model's (a head a row) takes the slice's pages with zeros
    in the pad."""
    out = {}
    # dynalint: kv-codec — whole-page slice moves keep the stored
    # (possibly quantized) representation; scale leaves share axes 0/1
    for key in cache:
        arr = cache[key]
        idx = [slice(None)] * arr.ndim
        for axis, start, count in slices:
            idx[axis] = slice(start, start + count)
        idx[2] = ids
        if row_heads == 1:
            out[key] = arr.at[tuple(idx)].set(
                _stored_pages(pages[key], 1, pad) if pad else pages[key],
                mode="drop")
            continue
        heads = range(arr.shape[1] * row_heads)[idx[1]]   # of the slice
        hd = arr.shape[-1] // row_heads
        for lane in range(row_heads):
            first = (lane - heads.start) % row_heads
            mine = heads[first::row_heads]
            if not mine:
                continue
            idx[1] = slice(mine[0] // row_heads,
                           mine[0] // row_heads + len(mine))
            idx[4] = slice(lane * hd, (lane + 1) * hd)
            arr = arr.at[tuple(idx)].set(
                pages[key][:, first::row_heads], mode="drop")
        out[key] = arr
    return out


@jax.named_scope("kv.write")
def _scatter_new_kv(cache, k_news, v_news, write_idx, keys=None):
    """One in-place scatter of all layers' new kv rows (deferred write).

    cache {k,v[,k_scale,v_scale]}: [L, Hkv, P, ps, hd] (+ [L, Hkv, P,
    ps] scales); k_news/v_news [L, S, Hkv, hd] full-precision rows
    (v_news None: a one-leaf cache, latent attention);
    write_idx [S] flat token slots (<0 = padding, dropped). `keys`: the
    leaves to write where they are not the pool's own (a window pool's
    ("wk", "wv"), engine/config.ModelConfig.window_cache_leaves). On kv_quant
    caches the rows quantize HERE — capture time, inside the jitted step
    — and the int8 values + f32 scales scatter together. The leaves are
    written in the layout they are stored in, row by row
    (ops/attention.write_kv_rows): no leaf is copied or re-laid-out.
    """
    from dynamo_tpu.ops.attention import (
        kv_write_plan, stored_kv_rows, write_kv_rows)
    from dynamo_tpu.ops.kv_quant import cache_keys
    quant = "k_scale" in cache
    keys = keys or tuple(key for key in cache_keys(quant) if key in cache)
    # dynalint: kv-codec — rows enter in the stored representation
    # (stored_kv_rows quantizes them on an int8 pool), values and scales
    # paired: [L, S, Hkv, hd] / [L, S, Hkv]
    leaves = write_kv_rows(
        tuple(cache[key] for key in keys),
        stored_kv_rows(k_news, v_news, quant), kv_write_plan(write_idx),
        jnp.arange(len(k_news), dtype=jnp.int32))
    return dict(zip(keys, leaves))


def _engine_decode_window(cfg: ModelConfig, eos_ids: tuple, kernel_mesh,
                          n_steps: int, page_size: int, with_rp: bool,
                          with_lp: bool, greedy: bool,
                          params, cache, tokens, positions, page_table,
                          base_table, max_pos, temperature, top_k, top_p,
                          seeds, counters, min_tokens, ignore_eos=None,
                          stop_ids=None, hist=None, rep_penalty=None,
                          state_slots=None, wtable=None, woff=None):
    """N fused decode iterations: forward + sample per step, the sampled
    token feeding the next step on device (lax.scan), so one dispatch and
    one [N, S] token download serve N tokens (VERDICT r2 weak #1 fix).

    Each step uses the deferred-write decode path: the cache is read-only
    during the layer scan (attention adds the current token via a
    self-term) and all layers' new kv rows land in ONE in-place scatter —
    threading cache slices through scan outputs made XLA copy the whole
    cache every step (~8 ms on the 1B flagship). Like forward() and
    decode_forward(), the program reads the pool in place (one gather of
    the pages `base_table` names, before the scan) and writes it once
    (_scatter_new_kv, after it, row by row in the stored layout): no leaf
    is copied or re-laid-out (PERF.md section 6, PR 26).

    Split-KV window (VERDICT r3 missing #2): the valid prefix pages are
    gathered ONCE per window into a read-only base buffer whose width
    follows `base_table` — the page_table sliced by the engine to the
    bucket of the TRUE kv length at window start, not the admission-time
    allocation (which reserves for max_tokens and made attention read up
    to 2x the valid KV). In-window tokens accumulate in a [L, Hkv, S,
    n_steps, hd] buffer (the only KV state carried through the scan —
    ~16 MB on the 1B flagship vs ~2 GB for the round-3 full-width carry);
    attention merges base + window + self-term in one joint softmax.

    max_pos[i] is the highest position slot i may write (-1 for padding);
    positions clamp against it so a sequence that exhausts its max_tokens
    budget mid-window drops its writes and never reads pages beyond its
    table. Stop conditions are host-side: the caller discards tokens after
    a stop, matching the reference's engines which also overrun stop
    sequences by at most a bounded window.

    `state_slots` [S] (a model with linear-attention layers): each row's
    recurrent-state slot. The state leaves of `cache` ride the step
    scan's carry beside the window buffer: every step reads and writes
    each live row's slot in place (llama.kda_decode), and they go back
    into `cache` once, after the scan. A row that is not `writable`
    (finished, out of budget, padding) leaves its slot as it is.

    `wtable` [S, Wb], `woff` [S] (a model with a window pool): each
    row's pages in the window pool and the position of its table's first
    key. The window layers get a base, a window buffer and an
    end-of-window writeback of their own over their own leaves ("wk",
    "wv"): the base is the row's WHOLE short table (a sliding layer's
    base is bounded by the window, whatever the context), its valid
    length `base_len - woff`.

    with_rp / with_lp / greedy pick separately-compiled variants so the
    common greedy path pays for neither the seen-token mask, the logprob
    log_softmax+top_k, nor the sampler's cut search; every sampled plan
    takes the one tail (sampler.sample), whatever its rows' top_p.
    """
    s = tokens.shape[0]
    rows = jnp.arange(s)
    seen0 = (seen_token_mask(hist, cfg.vocab_size) if with_rp else
             jnp.zeros((s, 1), bool))
    if ignore_eos is None:
        ignore_eos = jnp.ones((s,), bool)
    if eos_ids:
        eos_vec = jnp.zeros((cfg.vocab_size,), bool).at[
            jnp.asarray(eos_ids, jnp.int32)].set(True)
    else:
        eos_vec = None

    # a model none of whose layers holds a page (power retention) has no
    # pool leaf: its window has no base, no buffer and no writeback, and
    # its whole context rides the state leaves below
    paged = "k" in cache
    if paged:
        l, hkv_n, n_pages, ps, hd = cache["k"].shape  # dynalint: kv-codec
    kvq = bool(cfg.kv_quant)
    # the Pallas-kernel decode path streams pages from the global cache
    # itself — it keeps the original carry-the-cache window (per-step
    # scatter); the split-KV fast path applies to the XLA gather mode
    pregather = llama._decode_kernel_mode(cfg) is None

    if not paged:
        kb = vb = kw0 = vw0 = None
    elif pregather:
        base_pb = base_table.shape[1]
        lb = base_pb * page_size

        # page ids come from the allocator and are in range: mode="clip"
        # spares the gathered base (537 MB a leaf for Mistral-7B-16 at 32
        # slots x 512 tokens) the fill pass of take's default mode, a
        # broadcast and a select as large as the base itself
        # a latent cache (ONE row a token) is gathered a (layer, page) an
        # index, over the layer and page axes as the one axis they
        # already are: a `take` along the page axis alone moves all the
        # layers' rows an index, and XLA:TPU splits such a gather once an
        # index's rows pass half a megabyte (Moonlight's 9 x 64 x 640
        # bf16). A K / V pool is split by its rows, which are a view; the
        # one row of a latent pool by COLUMNS, each piece a slice of the
        # whole pool, 1.5 GB moved a window (PERF.md section 6, PR 53)
        @jax.named_scope("attention.gather")
        def gather_base(c, table=base_table):
            if cfg.is_mla:
                pages = jnp.arange(c.shape[0])[:, None] * c.shape[2] \
                    + table.reshape(-1)
                g = jnp.take(c.reshape((-1,) + c.shape[3:]), pages, axis=0,
                             mode="clip")
            else:
                g = jnp.take(c, table.reshape(-1), axis=2, mode="clip")
            return g.reshape(c.shape[0], hkv_n, s,
                             table.shape[1] * page_size, hd)

        @jax.named_scope("attention.gather")
        def gather_base_scale(sc):
            g = jnp.take(sc, base_table.reshape(-1), axis=2, mode="clip")
            return g.reshape(l, hkv_n, s, lb)

        if kvq:
            # int8 cache: dequantize the per-window read-only base ONCE
            # at gather (ops/kv_quant.py codec read); the in-window
            # buffers below hold full-precision rows and never round-
            # trip through int8 until the end-of-window writeback
            from dynamo_tpu.ops.kv_quant import dequantize_rows
            dt = jnp.dtype(cfg.dtype)
            # dynalint: kv-codec — codec read site
            kb = dequantize_rows(gather_base(cache["k"]),
                                 gather_base_scale(cache["k_scale"]), dt)
            # dynalint: kv-codec — codec read site
            vb = dequantize_rows(gather_base(cache["v"]),
                                 gather_base_scale(cache["v_scale"]), dt)
        else:
            # dynalint: kv-codec — unquantized base gather
            kb = gather_base(cache["k"])
            # a one-leaf cache (latent attention) has no values leaf: the
            # base, the window buffer and the new rows are then k alone
            # dynalint: kv-codec — unquantized base gather
            vb = gather_base(cache["v"]) if "v" in cache else None
        # valid kv at window start; fixed across the window (the window
        # buffer covers everything generated after it)
        base_len = jnp.clip(positions, 0, max_pos + 1)
        kw0 = jnp.zeros((l, hkv_n, s, n_steps, hd), kb.dtype)
        vw0 = None if vb is None else jnp.zeros_like(kw0)
    swa0 = None
    if cfg.window_pool:
        # dynalint: kv-codec — unquantized base gather (window pool)
        wkb, wvb = (gather_base(cache[key], wtable) for key in ("wk", "wv"))
        swa0 = (jnp.zeros(wkb.shape[:3] + (n_steps, hd), wkb.dtype),) * 2

    def global_write_idx(pos, writable):
        """Flat global-cache slot for this step's row (-1 = dropped)."""
        page = page_table[rows, jnp.maximum(
            jnp.minimum(pos, max_pos), 0) // page_size]
        return jnp.where(writable, page * page_size + pos % page_size, -1)

    def window_write_idx(pos, writable):
        """The same slot in the window pool: the row's table starts at
        its first held page."""
        at = (jnp.maximum(jnp.minimum(pos, max_pos), 0) - woff) // page_size
        page = wtable[rows, jnp.clip(at, 0, wtable.shape[1] - 1)]
        return jnp.where(writable & (at >= 0),
                         page * page_size + pos % page_size, -1)

    @jax.named_scope("sampler")
    def sample_and_track(logits, ctr, seen, alive):
        """Shared step tail: sampling + rep-penalty seen set + eos alive.
        One definition so the kernel and pregather bodies can't diverge."""
        nxt, lp, top_ids, top_lps = _sample_logits(
            logits, eos_ids, temperature, top_k, top_p, seeds, ctr,
            min_tokens, seen=seen if with_rp else None,
            rep_penalty=rep_penalty if with_rp else None, with_lp=with_lp,
            greedy=greedy)
        if with_rp:
            seen = seen.at[rows, nxt].set(True)
        if eos_vec is not None:
            alive = alive & (ignore_eos | ~eos_vec[nxt])
        if stop_ids is not None and stop_ids.shape[1]:
            # hidden stop ids kill the slot device-side too (unconditional
            # — ignore_eos does not cover explicit stops), so post-stop
            # steps neither write KV nor skew MoE capacity accounting
            # (VERDICT r3 weak #3)
            alive = alive & ~jnp.any(nxt[:, None] == stop_ids, axis=1)
        return nxt, lp, top_ids, top_lps, seen, alive

    # alive (both bodies) tracks every device-detectable finish — eos
    # sampled, max_tokens via max_pos, and hidden stop_token_ids (VERDICT
    # r3 weak #3) — so post-finish garbage steps neither write KV nor
    # pollute MoE capacity/drop accounting.
    def body_kernel(carry, _):
        """Kernel-mode window body: cache carried, scattered every step."""
        cache_c, tok, pos, ctr, seen, alive = carry
        writable = (pos <= max_pos) & alive
        prefix = jnp.clip(pos, 0, max_pos + 1)
        logits, k_news, v_news, aux = llama.decode_forward(
            params, cfg, tok, cache_c, page_table, prefix, pos,
            valid=writable, mesh=kernel_mesh, with_aux=True)
        cache_c = _scatter_new_kv(cache_c, k_news, v_news,
                                  global_write_idx(pos, writable))
        nxt, lp, top_ids, top_lps, seen, alive = sample_and_track(
            logits, ctr, seen, alive)
        return (cache_c, nxt, pos + 1, ctr + 1, seen, alive), \
            (nxt, lp, top_ids, top_lps, aux)

    state_keys = tuple(cfg.state_leaves())
    state0 = tuple(cache[key] for key in state_keys)

    def body(carry, t):
        kw, vw, state, swa_win, tok, pos, ctr, seen, alive = carry
        writable = (pos <= max_pos) & alive
        prefix = jnp.clip(pos, 0, max_pos + 1)
        # tokens written in-window so far; window index j == step index
        # (all slots step together), valid entries are j < win_len
        win_len = prefix - base_len if paged else None
        logits, k_news, v_news, aux, *more = llama.decode_forward(
            params, cfg, tok, cache, page_table, prefix, pos,
            valid=writable, mesh=kernel_mesh, with_aux=True,
            window=(kb, vb, kw, vw, base_len, win_len) if paged else None,
            state=(state, state_slots) if state_keys else None,
            swa=None if swa_win is None
            else (wkb, wvb, *swa_win, base_len - woff))
        if state_keys:
            state = more[0]
        w_out = ()
        if swa_win is not None:
            # the window layers' rows: into their buffer at step t, and
            # out for their own end-of-window writeback
            w_news = more[-1]
            with jax.named_scope("kv.window"):
                swa_win = tuple(jax.lax.dynamic_update_index_in_dim(
                    buf, new.transpose(0, 2, 1, 3).astype(buf.dtype), t,
                    axis=3) for buf, new in zip(swa_win, w_news))
            w_out = (*w_news, window_write_idx(pos, writable))
        # this step's rows land at window index t for every slot; slots
        # that may not write (finished/padding) still store garbage there
        # but their win_len stops growing, so attention never reads it.
        # The global-cache slot for the end-of-window writeback is
        # tracked separately (dropped rows get index -1).
        with jax.named_scope("kv.window"):
            if kw is not None:
                kw = jax.lax.dynamic_update_index_in_dim(
                    kw, k_news.transpose(0, 2, 1, 3).astype(kw.dtype), t,
                    axis=3)
            if vw is not None:
                vw = jax.lax.dynamic_update_index_in_dim(
                    vw, v_news.transpose(0, 2, 1, 3).astype(vw.dtype), t,
                    axis=3)
        nxt, lp, top_ids, top_lps, seen, alive = sample_and_track(
            logits, ctr, seen, alive)
        return (kw, vw, state, swa_win, nxt, pos + 1, ctr + 1, seen,
                alive), \
            (nxt, lp, top_ids, top_lps, aux, k_news, v_news,
             global_write_idx(pos, writable) if paged else None, w_out)

    alive0 = max_pos >= 0
    if not pregather:
        (cache, tok_f, pos_f, ctr_f, *_), \
            (toks, lps, top_ids, top_lps, auxs) = \
            jax.lax.scan(body_kernel,
                         (cache, tokens, positions, counters, seen0,
                          alive0), None, length=n_steps)
        aux = {k: jnp.sum(v) for k, v in auxs.items()}
        return (toks, lps, top_ids, top_lps, cache, aux,
                (tok_f, pos_f, ctr_f))
    (kw, vw, state_f, _, tok_f, pos_f, ctr_f, *_), \
        (toks, lps, top_ids, top_lps, auxs, k_all, v_all, widx_all,
         w_all) = \
        jax.lax.scan(body,
                     (kw0, vw0, state0, swa0, tokens, positions, counters,
                      seen0, alive0),
                     jnp.arange(n_steps), length=n_steps)
    aux = {k: jnp.sum(v) for k, v in auxs.items()}
    # end-of-window writeback: all N steps' rows -> global paged cache in
    # one scatter ([N, L, S, Hkv, hd] -> [L, N*S, Hkv, hd])
    def flat_rows(rows_all):
        return None if rows_all is None else rows_all.transpose(
            1, 0, 2, 3, 4).reshape(-1, n_steps * s, hkv_n, hd)

    pools = _scatter_new_kv(cache, flat_rows(k_all), flat_rows(v_all),
                            widx_all.reshape(-1)) if paged else {}
    if w_all:
        # the window layers' rows -> the window pool, the same way
        wk_all, wv_all, wwidx_all = w_all
        pools.update(_scatter_new_kv(
            cache, flat_rows(wk_all), flat_rows(wv_all),
            wwidx_all.reshape(-1), keys=("wk", "wv")))
    cache = pools
    cache.update(zip(state_keys, state_f))
    # final (token, position, counter) stay ON DEVICE: when the slot set and
    # page allocation are unchanged, the engine feeds them straight into the
    # next window — zero plan uploads per steady-state window (each host->
    # device upload rides the serving host's dispatch latency)
    return toks, lps, top_ids, top_lps, cache, aux, (tok_f, pos_f, ctr_f)


def _engine_verify_step(cfg: ModelConfig, eos_ids: tuple, sp_mesh,
                        kernel_mesh, pp_mesh, params, cache, tokens,
                        positions, page_table, kv_lens, write_idx, counters,
                        min_tokens):
    """Speculative-decoding verify: one prefill-shaped forward over each
    slot's [last_token, draft...] block, returning the greedy token at
    EVERY position ([S, K+1] int32). Position j's argmax replays exactly
    what sample_logits(greedy=True) would produce when generating token
    counters+j — including the min-tokens eos ban — so host-side
    acceptance (engine/spec.py) is exact. Draft KV rows are written during
    the forward; rejected rows become garbage beyond the committed length,
    which nothing ever reads (attention clamps to kv_lens / base_len) and
    the next write at that position overwrites.
    """
    meta = AttnMetadata(positions=positions, page_table=page_table,
                        kv_lens=kv_lens, write_idx=write_idx)
    if pp_mesh is not None:
        from dynamo_tpu.models.pp import pp_forward
        logits, cache = pp_forward(params, cfg, tokens, cache, meta,
                                   pp_mesh)
        # the per-position argmax below must see full vocab rows — same
        # replication argument as _engine_step's sampling tail
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(pp_mesh, P(None, None, None)))
        aux = {}
    else:
        logits, cache, aux = llama.forward(params, cfg, tokens, cache, meta,
                                           sp_mesh=sp_mesh, mesh=kernel_mesh,
                                           with_aux=True)
    if eos_ids:
        # mirror sample_logits' min-tokens eos ban, per block position:
        # position j emits generated-token index counters+j
        j = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
        ban = (counters[:, None] + j) < min_tokens[:, None]   # [S, K+1]
        eos = jnp.asarray(eos_ids, jnp.int32)
        eos_mask = jnp.zeros((logits.shape[-1],), bool).at[eos].set(True)
        logits = jnp.where(ban[:, :, None] & eos_mask[None, None, :],
                           -1e30, logits)
    pred = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return pred, cache, aux


def _engine_step(cfg: ModelConfig, eos_ids: tuple, sp_mesh, kernel_mesh,
                 with_rp: bool, with_lp: bool, with_mm: bool, pp_mesh,
                 params, cache,
                 tokens, positions, page_table, kv_lens, write_idx, last_idx,
                 temperature, top_k, top_p, seeds, counters, min_tokens,
                 hist=None, rep_penalty=None, mm_embeds=None, mm_mask=None,
                 state_slots=None, wtable=None, woff=None, wwrite_idx=None,
                 prev_tokens=None, src=None):
    """forward + gather last logits + sample, fused into one XLA program.

    `prev_tokens` [cap] and `src` [B] (the engine hands both, always): a
    row with `src >= 0` feeds `prev_tokens[src]`, the token row `src` of
    the step before sampled, which never left the device, in place of
    its `tokens[:, 0]`; the program then also returns its own sampled
    tokens at that one length for the step behind it to read."""
    if prev_tokens is not None:
        # a select over the grid and no scatter into column 0, which an
        # "sp" mesh's ring prefill would have to re-shard
        first = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :] == 0
        tokens = jnp.where(first & (src >= 0)[:, None],
                           prev_tokens[jnp.maximum(src, 0)][:, None], tokens)
    meta = AttnMetadata(positions=positions, page_table=page_table,
                        kv_lens=kv_lens, write_idx=write_idx,
                        state_slots=state_slots, wtable=wtable, woff=woff,
                        wwrite_idx=wwrite_idx)
    if pp_mesh is not None:
        from dynamo_tpu.models.pp import pp_forward
        logits, cache = pp_forward(
            params, cfg, tokens, cache, meta, pp_mesh,
            input_embeds=mm_embeds if with_mm else None,
            embeds_mask=mm_mask if with_mm else None)
        # replicate before the sampling tail: pp_forward returns logits
        # vocab-sharded over "tp", and with jax_threefry_partitionable
        # =False (this build's default) a categorical draw partitioned
        # over the vocab produces DIFFERENT bits than the single-mesh
        # oracle's replicated draw — sampled streams must be mesh-
        # invariant at a fixed seed (tests/test_pp.py sampled oracle)
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(pp_mesh, P(None, None, None)))
        last = logits[jnp.arange(tokens.shape[0]), last_idx]
        aux = {}
    else:
        # the head at the sampled rows only, the token-wise layers over
        # the step's real tokens (llama.forward)
        last, cache, aux = llama.forward(
            params, cfg, tokens, cache, meta,
            input_embeds=mm_embeds if with_mm else None,
            embeds_mask=mm_mask if with_mm else None,
            sp_mesh=sp_mesh, mesh=kernel_mesh, with_aux=True,
            last_idx=last_idx)                      # [B, V] f32
    seen = seen_token_mask(hist, cfg.vocab_size) if with_rp else None
    toks, lp, top_ids, top_lps = _sample_logits(
        last, eos_ids, temperature, top_k, top_p, seeds, counters,
        min_tokens, seen=seen, rep_penalty=rep_penalty if with_rp else None,
        with_lp=with_lp)
    if prev_tokens is None:
        return toks, lp, top_ids, top_lps, cache, aux
    return toks, lp, top_ids, top_lps, cache, aux, \
        jnp.full_like(prev_tokens, -1).at[:toks.shape[0]].set(toks)
