"""dynalint (dynamo_tpu/analysis): rule fixtures + the repo-wide CI gate.

Layout:
- one positive AND one negative fixture per AST rule (R1-R25), the
  positives for R1/R2 being faithful minimal copies of the PRE-FIX
  ADVICE r5 bugs (spec.py salt-id drafts, _decode_kernel_prefix missing
  stale-tail zeroing) — the analyzer must flag both on the pre-fix
  shapes and stay quiet on the fixed ones;
- one positive and one negative per jaxpr invariant (J1-J5);
- the gate: the analyzer over dynamo_tpu/ plus the engine entry-point
  audit yields zero non-baseline findings, so this tier-1 pytest run IS
  the CI gate for new findings.
"""
import json
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.analysis import (
    audit_bucket_ladder, audit_donation, filter_baseline, lint_source,
    load_baseline, run_lint, save_baseline, trace_and_audit,
)
from dynamo_tpu.analysis.findings import Finding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "tools", "dynalint_baseline.json")


def lint(src):
    return lint_source(textwrap.dedent(src), "fixture.py")


def rules(findings):
    return {f.rule for f in findings}


# -- R1: unguarded vocab gathers ----------------------------------------------

# faithful minimal copy of the PRE-FIX ngram_propose shape (ADVICE r5
# high): token ids sliced from raw history, returned with no vocab bound
PREFIX_NGRAM = """
    import numpy as np

    def ngram_propose(tokens, k, min_ngram=2, max_ngram=4):
        arr = np.asarray(tokens, dtype=np.int64)
        cont = arr[len(arr) - k:]
        return [int(x) for x in cont]
"""


def test_r1_flags_prefix_ngram_propose():
    assert "R1" in rules(lint(PREFIX_NGRAM))


def test_r1_quiet_on_fixed_ngram_propose():
    fixed = """
        import numpy as np

        def ngram_propose(tokens, k, min_ngram=2, max_ngram=4,
                          vocab_size=None):
            arr = np.asarray(tokens, dtype=np.int64)
            cont = [int(x) for x in arr[len(arr) - k:]]
            if vocab_size is not None:
                for i, x in enumerate(cont):
                    if not 0 <= x < vocab_size:
                        return cont[:i]
            return cont
    """
    assert "R1" not in rules(lint(fixed))


def test_r1_flags_unclamped_embedding_take():
    pos = """
        import jax.numpy as jnp

        def embed(params, ids):
            return jnp.take(params["embed"], ids, axis=0)
    """
    assert "R1" in rules(lint(pos))


def test_r1_quiet_on_clamped_take_and_axis_subscripts():
    neg = """
        import jax.numpy as jnp

        def embed(params, ids, vocab):
            x = jnp.take(params["embed"], jnp.clip(ids, 0, vocab - 1),
                         axis=0)
            return x[:, None] + params["embed"][..., None].sum()
    """
    assert "R1" not in rules(lint(neg))


def test_r1_live_on_current_spec_py():
    """The satellite fix must keep spec.py / engine.py R1-clean."""
    for rel in ("dynamo_tpu/engine/spec.py", "dynamo_tpu/engine/engine.py"):
        with open(os.path.join(REPO, rel)) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R1"], rel


# -- R2: Pallas kernels missing stale-tail K/V zeroing ------------------------

# faithful minimal copy of the PRE-FIX _decode_kernel_prefix per-head
# loop (ADVICE r5 medium): packed kernel contracting unmasked K and V
PREFIX_KERNEL = """
    import jax
    import jax.numpy as jnp

    def _decode_kernel_prefix(ps, hkv, g, hd, pack, q_shifts, k_buf,
                              v_buf, slot, prefix):
        outs = []
        for j in range(hkv):
            k = k_buf[slot, j].astype(jnp.float32)
            v = v_buf[slot, j].astype(jnp.float32)
            sc = jax.lax.dot_general(
                q_shifts[j], k, (((1,), (1,)), ((), ())))
            p = jnp.exp(sc)
            outs.append(jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ()))))
        return outs
"""


def test_r2_flags_prefix_kernel_without_masking():
    found = [f for f in lint(PREFIX_KERNEL) if f.rule == "R2"]
    assert len(found) == 2  # both the K and the V contraction


def test_r2_quiet_when_vpos_masked():
    fixed = """
        import jax
        import jax.numpy as jnp

        def _decode_kernel_prefix(ps, hkv, g, hd, pack, q_shifts, k_buf,
                                  v_buf, slot, prefix, tail_ok):
            outs = []
            for j in range(hkv):
                k = k_buf[slot, j].astype(jnp.float32)
                v = v_buf[slot, j].astype(jnp.float32)
                k = jnp.where(tail_ok, k, 0.0)
                v = jnp.where(tail_ok, v, 0.0)
                sc = jax.lax.dot_general(
                    q_shifts[j], k, (((1,), (1,)), ((), ())))
                p = jnp.exp(sc)
                outs.append(jax.lax.dot_general(
                    p, v, (((1,), (0,)), ((), ()))))
            return outs
    """
    assert "R2" not in rules(lint(fixed))


def test_r2_unpacked_kernel_k_is_exempt():
    """Non-packed kernels (no `pack` arg) mask K's scores with NEG_INF
    instead — lanes never mix tokens, so only V needs zeroing."""
    unpacked = """
        import jax
        import jax.numpy as jnp

        def _decode_kernel(ps, g, q, k_buf, v_buf, slot, kv_len, vrow):
            k = k_buf[slot].astype(jnp.float32)
            v = v_buf[slot].astype(jnp.float32)
            v = jnp.where(vrow < kv_len, v, 0.0)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
            p = jnp.exp(sc)
            return jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
    """
    assert "R2" not in rules(lint(unpacked))


def test_r2_live_on_current_paged_attention():
    with open(os.path.join(REPO, "dynamo_tpu/ops/paged_attention.py")) as f:
        found = lint_source(f.read(), "dynamo_tpu/ops/paged_attention.py")
    assert not [x for x in found if x.rule == "R2"]


# -- R3: blocking calls in async defs -----------------------------------------

def test_r3_flags_blocking_sleep_in_async():
    pos = """
        import time

        async def handler():
            time.sleep(1.0)
    """
    assert "R3" in rules(lint(pos))


def test_r3_quiet_on_asyncio_sleep_and_sync_fns():
    neg = """
        import asyncio
        import time

        async def handler():
            await asyncio.sleep(1.0)

        def sync_loop():
            time.sleep(1.0)

        async def outer():
            def helper():
                time.sleep(0.1)  # runs in an executor, not the loop
            return helper
    """
    assert "R3" not in rules(lint(neg))


def test_r3_inline_disable():
    src = """
        import time

        async def handler():
            time.sleep(1.0)  # dynalint: disable=R3
    """
    assert "R3" not in rules(lint(src))


# -- R4: CancelledError-swallowing handlers -----------------------------------

def test_r4_flags_bare_and_base_exception():
    pos = """
        def f(work):
            try:
                work()
            except:
                pass

        def g(work):
            try:
                work()
            except BaseException:
                return None
    """
    assert len([f for f in lint(pos) if f.rule == "R4"]) == 2


def test_r4_quiet_on_reraise_and_exception():
    neg = """
        def f(work, cleanup):
            try:
                work()
            except BaseException:
                cleanup()
                raise

        def g(work):
            try:
                work()
            except Exception:
                pass  # CancelledError derives from BaseException: safe
    """
    assert "R4" not in rules(lint(neg))


# -- R5: mutating a container while iterating it ------------------------------

def test_r5_flags_mutation_while_iterating():
    pos = """
        def prune(d):
            for k in d:
                if k < 0:
                    d.pop(k)

        def prune_del(d):
            for k in d.keys():
                del d[k]
    """
    assert len([f for f in lint(pos) if f.rule == "R5"]) == 2


def test_r5_quiet_on_snapshot_iteration():
    neg = """
        def prune(d):
            for k in list(d):
                if k < 0:
                    d.pop(k)

        def other(d, e):
            for k in d:
                e.pop(k, None)
    """
    assert "R5" not in rules(lint(neg))


# -- R6: host syncs in hot-path files -----------------------------------------

HOT_SRC = """
    # dynalint: hot-path
    import jax

    def step(x):
        return float(x.sum()) + x.max().item()
"""


def test_r6_flags_host_sync_in_hot_path_file():
    assert len([f for f in lint(HOT_SRC) if f.rule == "R6"]) == 2


def test_r6_quiet_without_marker():
    assert "R6" not in rules(lint(HOT_SRC.replace("hot-path", "")))


# -- R7: unbounded transport awaits in serving layers -------------------------

R7_SRC = """
    import asyncio

    async def dispatch(messaging, subject, payload):
        return await messaging.request(subject, payload)

    async def consume(queue):
        return await queue.dequeue_leased()

    async def dial(host, port):
        return await asyncio.open_connection(host, port)
"""


def test_r7_flags_unbounded_transport_awaits_in_scope():
    found = lint_source(textwrap.dedent(R7_SRC),
                        "dynamo_tpu/frontend/fixture.py")
    assert len([f for f in found if f.rule == "R7"]) == 3


def test_r7_quiet_outside_serving_layers():
    # same awaits in engine/device code: exempt (bounded by computation,
    # not by a remote peer)
    found = lint_source(textwrap.dedent(R7_SRC),
                        "dynamo_tpu/engine/fixture.py")
    assert "R7" not in rules(found)


def test_r7_quiet_on_bounded_awaits():
    neg = """
        import asyncio
        from dynamo_tpu.runtime.deadline import with_deadline

        async def dispatch(messaging, subject, payload, ctx):
            return await with_deadline(
                messaging.request(subject, payload, timeout=30.0),
                30.0, ctx)

        async def consume(queue):
            return await queue.dequeue_leased(timeout=1.0, lease_s=30.0)

        async def dial(host, port):
            return await asyncio.wait_for(
                asyncio.open_connection(host, port), 10.0)

        async def fire_and_forget(messaging, subject, payload):
            await messaging.publish(subject, payload)  # not a round trip
    """
    found = lint_source(textwrap.dedent(neg),
                        "dynamo_tpu/disagg/fixture.py")
    assert "R7" not in rules(found)


def test_r7_live_on_current_serving_layers():
    """The reliability PR must keep the serving layers R7-clean (every
    control-plane round trip bounded)."""
    import glob
    scoped = []
    for pat in ("dynamo_tpu/runtime/transports/*.py",
                "dynamo_tpu/frontend/*.py", "dynamo_tpu/disagg/*.py"):
        scoped.extend(glob.glob(os.path.join(REPO, pat)))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R7"], rel


# -- R8: blocking device syncs inside hot-path regions ------------------------

R8_SRC = """
    import jax
    import numpy as np

    def commit(outs, dev_aux):
        # dynalint: hot-path-begin
        toks = jax.device_get(outs)
        dev_aux.block_until_ready()
        host = np.asarray(dev_aux)
        # dynalint: hot-path-end
        return toks, host
"""


def test_r8_flags_syncs_in_region():
    assert len([f for f in lint(R8_SRC) if f.rule == "R8"]) == 3


def test_r8_quiet_outside_region():
    # same code with no region markers: R8 does not apply (R6 needs the
    # file-level marker, which this fixture also lacks)
    stripped = R8_SRC.replace("hot-path-begin", "").replace(
        "hot-path-end", "")
    assert "R8" not in rules(lint(stripped))


def test_r8_quiet_on_annotated_sync_point():
    neg = """
        import jax
        import numpy as np

        def commit(outs, other):
            # dynalint: hot-path-begin
            toks = jax.device_get(outs)  # dynalint: sync-point — the one
            #   intended per-window output fetch
            host = np.asarray(toks)   # toks came from device_get: host view
            counts = np.zeros((4,), np.int32)
            counts2 = np.asarray(counts)  # numpy-born: free view, no sync
            # dynalint: hot-path-end
            return host, counts2
    """
    assert "R8" not in rules(lint(neg))


def test_r8_region_does_not_trip_file_level_r6():
    # hot-path-begin/end scope a REGION for R8; they must not opt the
    # whole file into R6 (which would flag host code outside the region)
    src = """
        import jax

        def region(outs):
            # dynalint: hot-path-begin
            x = outs
            # dynalint: hot-path-end
            return x

        def boundary(outs):
            return jax.device_get(outs)
    """
    assert "R6" not in rules(lint(src))


def test_r8_live_on_engine_decode_region():
    """The pipelined decode staging/dispatch region in engine/engine.py
    must stay R8-clean: every blocking sync there carries an explicit
    `# dynalint: sync-point` justification."""
    path = os.path.join(REPO, "dynamo_tpu", "engine", "engine.py")
    with open(path) as f:
        src = f.read()
    assert "# dynalint: hot-path-begin" in src   # the region exists
    found = lint_source(src, "dynamo_tpu/engine/engine.py")
    assert not [f for f in found if f.rule == "R8"]


# -- R9: swallowed exceptions in the serving layers ---------------------------

R9_SRC = """
    import logging

    log = logging.getLogger("x")

    async def notify(messaging, subject, payload):
        try:
            await messaging.publish(subject, payload)
        except Exception:
            log.exception("notify failed")

    def parse(payload):
        try:
            return int(payload)
        except Exception:
            pass
"""


def test_r9_flags_pass_and_log_and_continue_in_scope():
    found = lint_source(textwrap.dedent(R9_SRC),
                        "dynamo_tpu/runtime/fixture.py")
    assert len([f for f in found if f.rule == "R9"]) == 2


def test_r9_quiet_outside_serving_layers():
    # engine code is out of scope: exceptions there surface through the
    # step loop, not past a peer-recovery mechanism
    found = lint_source(textwrap.dedent(R9_SRC),
                        "dynamo_tpu/engine/fixture.py")
    assert "R9" not in rules(found)


def test_r9_quiet_on_annotation_handling_and_narrow_types():
    neg = """
        import logging

        log = logging.getLogger("x")

        async def notify(messaging, subject, payload):
            try:
                await messaging.publish(subject, payload)
            except Exception:  # dynalint: swallow-ok=receiver-timeout-covers-it
                log.exception("notify failed")

        def parse(payload, fallback):
            try:
                return int(payload)
            except Exception:
                return fallback          # real handling: a fallback value

        def narrow(payload):
            try:
                return int(payload)
            except (ValueError, TypeError):
                pass                     # deliberate narrow types: quiet
    """
    found = lint_source(textwrap.dedent(neg),
                        "dynamo_tpu/disagg/fixture.py")
    assert "R9" not in rules(found)


def test_r9_live_on_current_serving_layers():
    """Every swallowed exception in runtime/, disagg/, frontend/ carries
    a `# dynalint: swallow-ok=<reason>` annotation (the satellite audit
    annotated all 20 pre-existing sites)."""
    import glob
    scoped = []
    for pat in ("dynamo_tpu/runtime/**/*.py", "dynamo_tpu/frontend/*.py",
                "dynamo_tpu/disagg/*.py"):
        scoped.extend(glob.glob(os.path.join(REPO, pat), recursive=True))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R9"], rel


# -- R10: unbucketed leading dims in schedule()-reachable plan builders -------

R10_SRC = """
    import numpy as np

    def _build_mixed(batch, tb):
        tokens = np.zeros((len(batch), tb), np.int32)
        return tokens
"""


def test_r10_flags_unbucketed_leading_dim_in_plan_builder():
    found = lint_source(textwrap.dedent(R10_SRC),
                        "dynamo_tpu/engine/scheduler_fixture.py")
    assert "R10" in rules(found)


def test_r10_quiet_outside_planning_scope_and_functions():
    # same shape outside the engine planning layer: not schedule()-
    # reachable, out of scope
    found = lint_source(textwrap.dedent(R10_SRC),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R10" not in rules(found)
    # helper not matching the planner naming (not schedule()-reachable
    # plan construction): quiet even in scope
    helper = """
        import numpy as np

        def pack_payload(items):
            return np.zeros((len(items),), np.int32)
    """
    found = lint_source(textwrap.dedent(helper),
                        "dynamo_tpu/engine/scheduler_fixture.py")
    assert "R10" not in rules(found)


def test_r10_quiet_on_bucketed_dims_and_annotation():
    neg = """
        import numpy as np

        def _build_prefill(batch, tb, buckets):
            bb = next_bucket(len(batch), buckets)
            tokens = np.zeros((bb, tb), np.int32)
            # dynalint: bucketed — row count is config-fixed max_slots
            extra = np.full((len(batch), 1), -1, np.int32)
            return tokens, extra
    """
    found = lint_source(textwrap.dedent(neg),
                        "dynamo_tpu/engine/scheduler_fixture.py")
    assert "R10" not in rules(found)


def test_r10_live_on_current_planning_layer():
    """The mixed-step planner (and everything else schedule()-reachable)
    builds only bucketed per-step arrays."""
    for rel in ("dynamo_tpu/engine/scheduler.py",
                "dynamo_tpu/engine/engine.py"):
        with open(os.path.join(REPO, rel)) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R10"], rel


# -- R11: raw KV-cache leaf access outside the quant codec helpers ------------

R11_SRC = """
    import jax.numpy as jnp

    def leaky_read(cache, page_table):
        k = cache["k"].astype(jnp.float32)     # bytes-as-values
        return jnp.take(k, page_table, axis=1)
"""


def test_r11_flags_raw_cache_leaf_access_in_model_code():
    found = lint_source(textwrap.dedent(R11_SRC),
                        "dynamo_tpu/models/fixture.py")
    assert "R11" in rules(found)


def test_r11_quiet_outside_scope_and_in_codec_module():
    # frontend code never touches cache leaves' numerics: out of scope
    found = lint_source(textwrap.dedent(R11_SRC),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R11" not in rules(found)
    # the codec module itself is exempt — it IS the decode/encode site
    found = lint_source(textwrap.dedent(R11_SRC),
                        "dynamo_tpu/ops/kv_quant.py")
    assert "R11" not in rules(found)


def test_r11_quiet_on_annotated_codec_sites():
    neg = """
        import jax.numpy as jnp
        from dynamo_tpu.ops.kv_quant import dequantize_rows

        def codec_read(cache, page_table):
            # dynalint: kv-codec — codec read site
            g = jnp.take(cache["k"], page_table, axis=1)
            # dynalint: kv-codec — scale rows feed the dequant
            s = jnp.take(cache["k_scale"], page_table, axis=1)
            return dequantize_rows(g, s, jnp.bfloat16)
    """
    found = lint_source(textwrap.dedent(neg),
                        "dynamo_tpu/models/fixture.py")
    assert "R11" not in rules(found)


def test_r11_live_on_current_model_and_ops_tree():
    """Every cache-leaf access in the model/ops/engine-step code is
    codec-annotated (the kv_quant PR's boundary stays mechanically
    enforced)."""
    for rel in ("dynamo_tpu/models/llama.py", "dynamo_tpu/models/pp.py",
                "dynamo_tpu/engine/engine.py",
                "dynamo_tpu/ops/attention.py",
                "dynamo_tpu/ops/paged_attention.py"):
        with open(os.path.join(REPO, rel)) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R11"], rel


# -- R12: control-plane retry loops without backoff+jitter --------------------

R12_SRC = """
    import asyncio

    async def watch_loop(kv, prefix, apply):
        while True:
            try:
                snapshot, events = await kv.watch_prefix(prefix)
                async for ev in events:
                    apply(ev)
            except Exception:
                await asyncio.sleep(0.1)   # hot, synchronized retry
"""


def test_r12_flags_retry_loop_without_backoff():
    found = lint_source(textwrap.dedent(R12_SRC),
                        "dynamo_tpu/runtime/watch_fixture.py")
    assert "R12" in rules(found)


def test_r12_quiet_outside_scope_and_without_retry_shape():
    # engine code is out of scope (no control-plane reconnects there)
    found = lint_source(textwrap.dedent(R12_SRC),
                        "dynamo_tpu/engine/fixture.py")
    assert "R12" not in rules(found)
    # a loop that does NOT survive failures (no handler) is not a retry
    # loop — death is handled a layer up
    no_handler = """
        async def watch_once(kv, prefix, apply):
            while True:
                snapshot, events = await kv.watch_prefix(prefix)
                async for ev in events:
                    apply(ev)
    """
    found = lint_source(textwrap.dedent(no_handler),
                        "dynamo_tpu/runtime/watch_fixture.py")
    assert "R12" not in rules(found)


def test_r12_quiet_with_backoff_or_annotation():
    with_backoff = """
        from dynamo_tpu.runtime.backoff import Backoff

        async def watch_loop(kv, prefix, apply):
            backoff = Backoff()
            while True:
                try:
                    snapshot, events = await kv.watch_prefix(prefix)
                    async for ev in events:
                        apply(ev)
                    backoff.reset()
                except Exception:
                    await backoff.sleep()
    """
    found = lint_source(textwrap.dedent(with_backoff),
                        "dynamo_tpu/runtime/watch_fixture.py")
    assert "R12" not in rules(found)
    annotated = """
        import asyncio

        async def heartbeat(lease, ttl):
            # dynalint: backoff-ok=TTL-paced renewal cadence
            while True:
                try:
                    lease.keep_alive()
                except Exception:
                    pass
                await asyncio.sleep(ttl / 3)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/runtime/hb_fixture.py")
    assert "R12" not in rules(found)


def test_r12_live_on_current_control_plane_tree():
    """Every surviving control-plane retry loop in runtime/, frontend/,
    kv_router/ either drives its delay through runtime/backoff.py or
    carries a justified fixed-cadence annotation."""
    import glob
    scoped = []
    for pat in ("dynamo_tpu/runtime/**/*.py", "dynamo_tpu/frontend/*.py",
                "dynamo_tpu/kv_router/*.py"):
        scoped.extend(glob.glob(os.path.join(REPO, pat), recursive=True))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R12"], rel


# -- R13: span lifecycle + hot-path span deferral ------------------------------

def test_r13_flags_begin_span_without_guaranteed_end():
    leaky = """
        from dynamo_tpu.runtime.tracing import TRACER

        async def serve_one(trace, req):
            span = TRACER.begin_span("serve", trace)
            if req.bad:
                return None          # span leaks on this path
            result = await req.run()
            TRACER.end_span(span)
            return result
    """
    assert "R13" in rules(lint(leaky))


def test_r13_quiet_on_with_form_and_try_finally():
    with_form = """
        from dynamo_tpu.runtime.tracing import TRACER

        async def serve_one(trace, req):
            with TRACER.span("serve", trace) as sp:
                sp.set(n=1)
                return await req.run()
    """
    assert "R13" not in rules(lint(with_form))
    finally_form = """
        from dynamo_tpu.runtime.tracing import TRACER

        async def serve_one(trace, req):
            span = TRACER.begin_span("serve", trace)
            try:
                return await req.run()
            finally:
                TRACER.end_span(span)
    """
    assert "R13" not in rules(lint(finally_form))
    annotated = """
        from dynamo_tpu.runtime.tracing import TRACER

        async def serve_one(trace, req, finish_cb):
            # dynalint: span-ok=ends-in-the-idempotent-finish-callback
            span = TRACER.begin_span("serve", trace)
            finish_cb.register(span)
            return await req.run()
    """
    assert "R13" not in rules(lint(annotated))


def test_r13_flags_span_recording_in_hot_path_region():
    hot = """
        from dynamo_tpu.runtime.tracing import TRACER

        def _pipeline_step(self, plan, trace):
            # dynalint: hot-path-begin
            with TRACER.span("window", trace):
                outs = self._dispatch_staged(plan)
            TRACER.event("emit", trace, n=len(outs))
            # dynalint: hot-path-end
            return outs
    """
    found = [x for x in lint(hot) if x.rule == "R13"]
    assert len(found) == 2          # the span AND the event


@pytest.mark.parametrize("call,flagged", [
    ('jax.profiler.TraceAnnotation("engine.dispatch")', True),
    ('TraceAnnotation("engine.dispatch")', True),
    ('jax.profiler.StepTraceAnnotation("step", step_num=1)', True),
    ('self.phases.phase("dispatch")', False),
    ('self._dispatch_phase(key)', False),
])
def test_r13_profiler_annotation_in_hot_path_region(call, flagged):
    """Inside a hot-path region the only recording form is
    PhaseTimer.phase, which carries the profiler annotation itself; a
    bare TraceAnnotation there is a finding, outside it is not."""
    hot = f"""
        import jax
        from jax.profiler import TraceAnnotation

        def _dispatch_staged(self, staged, key):
            # dynalint: hot-path-begin
            with {call}:
                outs = self._fn(staged)
            # dynalint: hot-path-end
            return outs
    """
    assert ("R13" in rules(lint(hot))) is flagged
    cold = hot.replace("# dynalint: hot-path-begin", "").replace(
        "# dynalint: hot-path-end", "")
    assert "R13" not in rules(lint(cold))


def test_r13_quiet_on_deferred_recorder_in_region():
    deferred = """
        from dynamo_tpu.runtime.tracing import TRACER

        def _pipeline_step(self, plan, t0, dt):
            # dynalint: hot-path-begin
            outs = self._dispatch_staged(plan)
            TRACER.defer_phase("engine", "dispatch", dt)
            # dynalint: hot-path-end
            return outs
    """
    assert "R13" not in rules(lint(deferred))
    # outside a region the same recording calls are fine
    cold = """
        from dynamo_tpu.runtime.tracing import TRACER

        def commit(self, plan, trace):
            TRACER.event("emit", trace, n=1)
    """
    assert "R13" not in rules(lint(cold))


def test_r13_live_on_current_tree():
    """Every begin_span in the live tree ends on all paths (or carries a
    justified span-ok), and no hot-path region records spans directly —
    the engine's regions route through PhaseTimer -> defer_phase."""
    import glob
    scoped = sorted(glob.glob(os.path.join(REPO, "dynamo_tpu/**/*.py"),
                              recursive=True))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R13"], rel


# -- R14: unbounded raw stream IO on the wire ----------------------------------

R14_SRC = """
    import asyncio
    from dynamo_tpu.runtime.transports.wire import read_frame, write_frame

    async def retire_ack(reader, writer, frame):
        write_frame(writer, frame)
        await writer.drain()               # unbounded flush
        return await read_frame(reader)    # unbounded ack read
"""


def test_r14_flags_unbounded_stream_io_in_scope():
    found = lint_source(textwrap.dedent(R14_SRC),
                        "dynamo_tpu/disagg/xfer_fixture.py")
    assert len([x for x in found if x.rule == "R14"]) == 2  # drain + read
    found = lint_source(textwrap.dedent(R14_SRC),
                        "dynamo_tpu/runtime/transports/tcp_fixture.py")
    assert "R14" in rules(found)


def test_r14_quiet_outside_scope():
    # the frontend's awaits are R7's territory; raw-IO scope is the
    # disagg data plane and the transport implementations
    found = lint_source(textwrap.dedent(R14_SRC),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R14" not in rules(found)


def test_r14_quiet_on_bounded_and_annotated_io():
    bounded = """
        import asyncio
        from dynamo_tpu.runtime.transports.wire import read_frame, write_frame

        async def retire_ack(self, reader, writer, frame, deadline):
            write_frame(writer, frame)
            await asyncio.wait_for(writer.drain(), self._io_timeout(deadline))
            return await read_frame(reader, timeout=self._io_timeout(deadline))
    """
    found = lint_source(textwrap.dedent(bounded),
                        "dynamo_tpu/disagg/xfer_fixture.py")
    assert "R14" not in rules(found)
    annotated = """
        from dynamo_tpu.runtime.transports.wire import read_frame

        async def pump(self, reader):
            while True:
                # dynalint: unbounded-io-ok=idle-client-connections-are-
                # legal; peer death surfaces as EOF
                frame = await read_frame(reader)
                self.dispatch(frame)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/runtime/transports/srv_fixture.py")
    assert "R14" not in rules(found)


def test_r14_live_on_data_and_control_wire():
    """Every raw stream read/write in disagg/ and runtime/transports/
    is bounded (timeout kwarg, wait_for) or carries a justified
    unbounded-io-ok annotation — the tentpole's per-IO timeout
    discipline, held by machine."""
    import glob
    scoped = []
    for pat in ("dynamo_tpu/disagg/*.py",
                "dynamo_tpu/runtime/transports/*.py"):
        scoped.extend(glob.glob(os.path.join(REPO, pat)))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R14"], rel


# -- R15: metric registration contract ----------------------------------------

R15_BAD = """
    from dynamo_tpu.observability.metrics import MetricsRegistry
    r = MetricsRegistry()
    undocumented = r.gauge("llm_mystery_gauge_nobody_wrote_down",
                           "has help but no catalog entry")
    helpless = r.gauge("llm_workers", "")
    missing_help = r.counter("llm_workers")
"""


def test_r15_flags_undocumented_family_and_empty_help():
    found = lint_source(textwrap.dedent(R15_BAD),
                        "dynamo_tpu/observability/fixture.py")
    r15 = [x for x in found if x.rule == "R15"]
    assert len(r15) == 3
    msgs = " ".join(x.message for x in r15)
    assert "not in the" in msgs and "no help text" in msgs


def test_r15_quiet_on_documented_families_and_fstring_fragments():
    good = """
        def build(r, name):
            # exact literal: catalog member
            g = r.gauge("llm_workers", "Live worker instances")
            # f-string fragments resolve against the catalog
            # (llm_cp_* families)
            cp = {n: r.gauge(f"llm_cp_{n}", f"control plane: {n}")
                  for n in ("watch_resyncs",)}
            # histogram with keyword help
            h = r.histogram("llm_ttft_seconds",
                            help_="time to first token")
            # dynalint: metric-doc-ok=fixture-internal scratch gauge
            s = r.gauge("llm_scratch_not_documented", "x")
            return g, cp, h, s
    """
    found = lint_source(textwrap.dedent(good),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R15" not in rules(found)


def test_r15_quiet_outside_package_scope():
    found = lint_source(textwrap.dedent(R15_BAD).replace(
        "dynamo_tpu.observability.metrics", "metrics"),
        "tools/fixture.py")
    assert "R15" not in rules(found)


def test_r15_live_every_registration_documented_with_help():
    """The live gate: every metric registration in the dynamo_tpu
    package carries help text and a docs/OBSERVABILITY.md §9 catalog
    entry (the static half; test_metrics_catalog.py holds the
    rendered half)."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R15"], \
            (rel, [x.message for x in found if x.rule == "R15"])


# -- R16: transfer-cost fallback contract --------------------------------------

R16_BAD = """
    def pick_worker(model, workers, nbytes):
        # ranks purely on the scalar estimate: a never-sampled link's
        # prior is indistinguishable from a measurement here
        return min(workers, key=lambda w: model.estimate_s(w, nbytes))
"""


def test_r16_flags_unhandled_scalar_estimate():
    found = lint_source(textwrap.dedent(R16_BAD),
                        "dynamo_tpu/kv_router/fixture.py")
    assert "R16" in rules(found)
    found = lint_source(textwrap.dedent(R16_BAD), "tools/fixture.py")
    assert "R16" in rules(found)


def test_r16_quiet_outside_scope():
    found = lint_source(textwrap.dedent(R16_BAD), "examples/fixture.py")
    assert "R16" not in rules(found)
    # generic `.estimate` on a non-cost receiver is not a target
    other = """
        def eta(tracker, job):
            return tracker.estimate(job)
    """
    found = lint_source(textwrap.dedent(other),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R16" not in rules(found)


def test_r16_quiet_on_handled_and_annotated_consumers():
    handled = """
        def pick_worker(model, workers, nbytes):
            best, best_cost = None, float("inf")
            for w in workers:
                est = model.estimate(w, nbytes)
                cost = est.seconds * (2.0 if est.cold else 1.0)
                if cost < best_cost:
                    best, best_cost = w, cost
            return best

        def drain_time(model, link):
            if not model.measured(link):
                return None
            return model.estimate_s(link, model.backlog_bytes(link))
    """
    found = lint_source(textwrap.dedent(handled),
                        "dynamo_tpu/kv_router/fixture.py")
    assert "R16" not in rules(found)
    annotated = """
        def rough_eta(model, link, nbytes):
            # dynalint: cost-fallback-ok=display-only ETA, the prior is
            # exactly what we want to show for unmeasured links
            return model.estimate_s(link, nbytes)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/observability/fixture.py")
    assert "R16" not in rules(found)


def test_r16_live_on_cost_model_consumers():
    """Every live consumer of the cost model's queries (the selector,
    the send path, the model's own delegating methods) handles the
    cold/frozen/default branch or carries a justified annotation."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R16"], \
            (rel, [x.message for x in found if x.rule == "R16"])


# -- R17: actuation pacing contract --------------------------------------------

R17_BAD = """
    async def rebalance_loop(workers):
        while True:
            for w in workers:
                await w.mark_draining()
"""


def test_r17_flags_unpaced_actuation_loop():
    found = lint_source(textwrap.dedent(R17_BAD),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R17" in rules(found)
    found = lint_source(textwrap.dedent(R17_BAD), "tools/fixture.py")
    assert "R17" in rules(found)


def test_r17_flags_controller_tick_without_pacing():
    tick = """
        async def tick(self, served_endpoint, role):
            await served_endpoint.re_role(role)
    """
    found = lint_source(textwrap.dedent(tick),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R17" in rules(found)


def test_r17_quiet_outside_scope_and_on_non_actuators():
    found = lint_source(textwrap.dedent(R17_BAD), "examples/fixture.py")
    assert "R17" not in rules(found)
    # `.drain()` on a non-worker receiver (stream writers, ledgers,
    # tracers) is not an actuation
    other = """
        async def pump(writer, ledger):
            while True:
                await writer.drain()
                ledger.drain(clear=True)
    """
    found = lint_source(textwrap.dedent(other),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R17" not in rules(found)
    # a one-shot actuation outside any loop/tick is an operator action
    oneshot = """
        async def maintenance(served_endpoint):
            await served_endpoint.drain(timeout_s=30.0)
    """
    found = lint_source(textwrap.dedent(oneshot),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R17" not in rules(found)


def test_r17_quiet_on_paced_and_annotated_actuators():
    paced = """
        async def actuate(self, decisions, workers):
            # the controller's cooldown+hysteresis pace these drains
            if not self.cooldown.ready(self.now()):
                return
            for d in decisions:
                await workers[d.worker].set_role(d.to_role)
    """
    found = lint_source(textwrap.dedent(paced),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R17" not in rules(found)
    annotated = """
        async def storm(workers):
            for w in workers:
                # dynalint: actuation-ok=seeded chaos storm driver, not
                # a controller; the whole point is unpaced churn
                await w.mark_draining()
    """
    found = lint_source(textwrap.dedent(annotated),
                        "tools/fixture.py")
    assert "R17" not in rules(found)


def test_r17_live_on_actuation_call_sites():
    """Every live drain/re-role call site in a loop or controller tick
    engages pacing (the autoscaler's Cooldown/Hysteresis, a Backoff, a
    seeded jitter) or carries a justified annotation."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R17"], \
            (rel, [x.message for x in found if x.rule == "R17"])


# -- R18: shared-pool verification contract ------------------------------------

R18_BAD = """
    def warm(pool, seq_hash, mode):
        # moves pool bytes with no word on where the capture sum is
        # checked — the shape R18 exists to catch
        return pool.fetch(seq_hash, mode)
"""


def test_r18_flags_unreferenced_pool_fetch():
    found = lint_source(textwrap.dedent(R18_BAD),
                        "dynamo_tpu/engine/fixture.py")
    assert "R18" in rules(found)
    found = lint_source(textwrap.dedent(R18_BAD), "tools/fixture.py")
    assert "R18" in rules(found)
    publish = """
        def tee(kv_pool, sh, parent, th, arrays):
            kv_pool.publish("w0", sh, parent, th, arrays)
    """
    found = lint_source(textwrap.dedent(publish),
                        "dynamo_tpu/engine/fixture.py")
    assert "R18" in rules(found)


def test_r18_quiet_outside_scope_and_on_non_pool_receivers():
    found = lint_source(textwrap.dedent(R18_BAD), "examples/fixture.py")
    assert "R18" not in rules(found)
    # generic fetch/publish on non-pool receivers is not a target
    other = """
        async def push(component, subject, payload):
            await component.publish(subject, payload)

        def load(store, key):
            return store.fetch(key)
    """
    found = lint_source(textwrap.dedent(other),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R18" not in rules(found)


def test_r18_quiet_on_referenced_and_annotated_pool_paths():
    handled = """
        def warm(pool, seq_hash, mode):
            # bytes are verified against the traveling capture checksum
            # inside fetch(); a mismatch quarantines and returns None
            return pool.fetch(seq_hash, mode)
    """
    found = lint_source(textwrap.dedent(handled),
                        "dynamo_tpu/engine/fixture.py")
    assert "R18" not in rules(found)
    annotated = """
        def poke(pool, seq_hash):
            # dynalint: pool-verify-ok=containment probe, no bytes move
            return pool.fetch(seq_hash, "")
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/engine/fixture.py")
    assert "R18" not in rules(found)


def test_r18_live_on_pool_call_sites():
    """Every live pool publish/fetch/claim/prefetch call site states
    where its checksum verification happens or carries a justified
    annotation (engine/kv_pool.py, scheduler._pool_claim, the engine
    publish tee, AdmissionPrefetcher)."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R18"], \
            (rel, [x.message for x in found if x.rule == "R18"])


# -- R19: starvation-bound contract --------------------------------------------

R19_BAD = """
    def make_room(scheduler, arrival):
        # preempts and class-orders with no visible bound: the high
        # class wins every contest here
        victim = select_victim(scheduler.running, below_prio=9)
        scheduler._preempt_one()
        return victim


    async def pump(queue):
        while True:
            item = await queue.dequeue_leased(timeout=1.0)
            if item:
                return item
"""


def test_r19_flags_unreferenced_preempt_and_dequeue():
    found = lint_source(textwrap.dedent(R19_BAD),
                        "dynamo_tpu/engine/fixture.py")
    r19 = [x for x in found if x.rule == "R19"]
    assert len(r19) == 3            # select_victim + _preempt_one + dequeue
    found = lint_source(textwrap.dedent(R19_BAD), "tools/fixture.py")
    assert "R19" in rules(found)


def test_r19_quiet_outside_scope_and_in_tests():
    found = lint_source(textwrap.dedent(R19_BAD), "examples/fixture.py")
    assert "R19" not in rules(found)
    found = lint_source(textwrap.dedent(R19_BAD),
                        "tests/fixture.py")
    assert "R19" not in rules(found)


def test_r19_quiet_on_referenced_and_annotated_sites():
    handled = """
        def make_room(scheduler, arrival):
            # victim starvation bounded by the class-band requeue +
            # queue aging limit (QosPolicy.aging_limit)
            victim = select_victim(scheduler.running, below_prio=9)
            scheduler._preempt_one()
            return victim
    """
    found = lint_source(textwrap.dedent(handled),
                        "dynamo_tpu/engine/fixture.py")
    assert "R19" not in rules(found)
    annotated = """
        async def pump(queue):
            while True:
                # dynalint: starvation-ok=single-class FIFO deployment
                item = await queue.dequeue_leased(timeout=1.0)
                if item:
                    return item
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/disagg/fixture.py")
    assert "R19" not in rules(found)


# -- R20: min-frontier aggregation contract ------------------------------------

R20_BAD = """
    def decide_fate(worker, rid, epoch):
        # trusts whatever one endpoint answers, silently
        pages = worker.server.committed_frontier(rid, epoch)
        if pages:
            worker.engine.salvage_remote(rid, pages)
        return pages


    def arm(engine, rid, first, needed, srv, epoch):
        engine.preactivate_remote(
            rid, first, needed,
            lambda: srv.stream_frontier(rid, epoch, 0))
"""


def test_r20_flags_unreferenced_frontier_consumers():
    found = lint_source(textwrap.dedent(R20_BAD),
                        "dynamo_tpu/disagg/fixture.py")
    r20 = [x for x in found if x.rule == "R20"]
    # committed_frontier + salvage_remote + preactivate_remote +
    # stream_frontier
    assert len(r20) == 4
    found = lint_source(textwrap.dedent(R20_BAD), "tools/fixture.py")
    assert "R20" in rules(found)


def test_r20_quiet_outside_scope_and_in_tests():
    found = lint_source(textwrap.dedent(R20_BAD), "examples/fixture.py")
    assert "R20" not in rules(found)
    found = lint_source(textwrap.dedent(R20_BAD), "tests/fixture.py")
    assert "R20" not in rules(found)


def test_r20_quiet_on_referenced_and_annotated_sites():
    handled = """
        def decide_fate(worker, rid, epoch):
            # frontier = MIN over per-stream frontiers (the
            # ShardedKvTransferGroup aggregation): salvage only keeps
            # pages every shard stream committed
            pages = worker.server.committed_frontier(rid, epoch)
            if pages:
                worker.engine.salvage_remote(rid, pages)
            return pages
    """
    found = lint_source(textwrap.dedent(handled),
                        "dynamo_tpu/disagg/fixture.py")
    assert "R20" not in rules(found)
    annotated = """
        def resume_point(srv, rid, epoch, sid):
            # dynalint: frontier-ok=per-stream resume handshake; fate
            # decisions still go through the min aggregation
            return srv.stream_frontier(rid, epoch, sid)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/disagg/fixture.py")
    assert "R20" not in rules(found)


# -- R22: placement-epoch contract ---------------------------------------------

R22_BAD = """
    def route_publish(ring, membership, key, payload):
        # caches placement with no word about when it expires
        targets = ring.owners_for(key)
        primary = ring.lookup(key)
        for hid in targets:
            payload.send(hid)
        return primary


    def price_pool(membership, score):
        if not membership.live_hosts():
            return 0
        return score
"""


def test_r22_flags_unreferenced_placement_consumers():
    found = lint_source(textwrap.dedent(R22_BAD),
                        "dynamo_tpu/engine/fixture.py")
    r22 = [x for x in found if x.rule == "R22"]
    # owners_for + ring.lookup + live_hosts
    assert len(r22) == 3
    found = lint_source(textwrap.dedent(R22_BAD), "tools/fixture.py")
    assert "R22" in rules(found)


def test_r22_quiet_outside_scope_tests_and_placement_layer():
    found = lint_source(textwrap.dedent(R22_BAD), "examples/fixture.py")
    assert "R22" not in rules(found)
    found = lint_source(textwrap.dedent(R22_BAD), "tests/fixture.py")
    assert "R22" not in rules(found)
    # the placement layer itself is exempt (it IS the epoch machinery,
    # the ops/kv_quant.py precedent from R11)
    found = lint_source(textwrap.dedent(R22_BAD),
                        "dynamo_tpu/runtime/placement.py")
    assert "R22" not in rules(found)


def test_r22_quiet_on_referenced_and_annotated_sites():
    handled = """
        def route_publish(ring, membership, key, payload):
            # owners re-resolved per call; every write carries the
            # membership epoch and serving hosts fence stale ones
            targets = ring.owners_for(key)
            for hid in targets:
                payload.send(hid)
    """
    found = lint_source(textwrap.dedent(handled),
                        "dynamo_tpu/engine/fixture.py")
    assert "R22" not in rules(found)
    annotated = """
        def snapshot_hosts(membership):
            # dynalint: ring-ok=read-only diagnosis snapshot, no
            # write or fetch is routed from this list
            return list(membership.live_hosts())
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/engine/fixture.py")
    assert "R22" not in rules(found)
    # bare `.lookup` on a non-ring receiver is not placement
    other = """
        def find(catalog, key):
            return catalog.lookup(key)
    """
    found = lint_source(textwrap.dedent(other),
                        "dynamo_tpu/engine/fixture.py")
    assert "R22" not in rules(found)


def test_r22_live_on_placement_call_sites():
    """Every live consumer of owners_for / ring.lookup / pool-host
    resolution speaks the ownership-epoch vocabulary or carries a
    justified annotation (pool_service fetch/publish/rebalance, the
    router's pool-host liveness fence)."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R22"], \
            (rel, [x.message for x in found if x.rule == "R22"])


# -- R23: one decode kernel ----------------------------------------------------

R23_BAD = """
    import functools
    import jax.experimental.pallas as pl


    def my_local_decode(q, k, v, ps, hkv):
        # a "quick local kernel" fork of the decode attention path
        return pl.pallas_call(
            functools.partial(_decode_kernel_fork, ps, hkv),
            grid=(4,),
        )(q, k, v)
"""


def test_r23_flags_decode_pallas_call_outside_dispatcher():
    found = lint_source(textwrap.dedent(R23_BAD),
                        "dynamo_tpu/engine/fixture.py")
    r23 = [x for x in found if x.rule == "R23"]
    assert len(r23) == 1
    found = lint_source(textwrap.dedent(R23_BAD), "tools/fixture.py")
    assert "R23" in rules(found)
    # a THIRD frozen copy pasted into the oracle module still flags
    found = lint_source(textwrap.dedent(R23_BAD),
                        "dynamo_tpu/ops/paged_attention_oracle.py")
    assert "R23" in rules(found)


def test_r23_quiet_outside_scope_and_in_dispatcher():
    found = lint_source(textwrap.dedent(R23_BAD), "examples/fixture.py")
    assert "R23" not in rules(found)
    # the unified dispatcher owns THE kernel — exempt (the
    # ops/kv_quant.py precedent from R11)
    found = lint_source(textwrap.dedent(R23_BAD),
                        "dynamo_tpu/ops/paged_attention.py")
    assert "R23" not in rules(found)
    # a pallas_call whose kernel is not decode attention stays quiet
    other = """
        import jax.experimental.pallas as pl


        def quantize(x):
            return pl.pallas_call(_quant_kernel, grid=(4,))(x)
    """
    found = lint_source(textwrap.dedent(other),
                        "dynamo_tpu/ops/fixture.py")
    assert "R23" not in rules(found)


def test_r23_quiet_on_annotated_sites():
    annotated = """
        import functools
        import jax.experimental.pallas as pl


        def frozen_oracle(q, ps, hkv):
            # dynalint: kernel-ok=frozen pre-PR-18 oracle fixture
            return pl.pallas_call(
                functools.partial(_decode_kernel_fork, ps, hkv),
                grid=(4,),
            )(q)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/engine/fixture.py")
    assert "R23" not in rules(found)


def test_r23_live_tree_has_one_decode_dispatcher():
    """The live tree dispatches decode attention through exactly one
    module: ops/paged_attention.py (exempt). The two frozen oracle
    call sites in ops/paged_attention_oracle.py carry
    `# dynalint: kernel-ok=` annotations; nothing else constructs a
    decode pallas_call."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R23"], \
            (rel, [x.message for x in found if x.rule == "R23"])


def test_r23_oracle_unreachable_from_engine():
    """Acceptance: the legacy kernels are demoted to test oracles —
    nothing under engine/ or models/ imports paged_attention_oracle."""
    import glob
    prod = glob.glob(os.path.join(REPO, "dynamo_tpu", "engine", "*.py"))
    prod += glob.glob(os.path.join(REPO, "dynamo_tpu", "models", "*.py"))
    assert prod
    for path in prod:
        with open(path) as f:
            src = f.read()
        assert "paged_attention_oracle" not in src, path


# -- R24: hedged-dispatch exactness --------------------------------------------

R24_BAD = """
    async def retry_faster(client, request):
        # "just fire a second copy if it's slow" — no race discipline,
        # no teardown, nothing stops a post-commit duplicate
        slot = client._start_hedge(request)
        return await slot
"""


def test_r24_flags_undisciplined_hedge_dispatch():
    found = lint_source(textwrap.dedent(R24_BAD),
                        "dynamo_tpu/frontend/fixture.py")
    r24 = [x for x in found if x.rule == "R24"]
    assert len(r24) == 1
    # a driver script forking hedges flags too — tools/ is in scope
    found = lint_source(textwrap.dedent(R24_BAD), "tools/fixture.py")
    assert "R24" in rules(found)


def test_r24_quiet_outside_scope():
    found = lint_source(textwrap.dedent(R24_BAD), "examples/fixture.py")
    assert "R24" not in rules(found)
    found = lint_source(textwrap.dedent(R24_BAD), "tests/fixture.py")
    assert "R24" not in rules(found)


def test_r24_quiet_when_function_speaks_the_discipline():
    disciplined = """
        async def hedge_race(client, request):
            # first frame wins; the loser is cancelled through the
            # abort path before any token is committed (pre-commit
            # only — a hedge never races a stream that has emitted)
            slot = client._start_hedge(request)
            return await slot
    """
    found = lint_source(textwrap.dedent(disciplined),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R24" not in rules(found)


def test_r24_quiet_on_annotated_sites():
    annotated = """
        async def replay_hedge(client, request):
            # dynalint: hedge-ok=offline replay of a recorded race
            slot = client._start_hedge(request)
            return await slot
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R24" not in rules(found)


def test_r24_live_tree_hedge_sites_disciplined():
    """The live tree dispatches hedges from exactly one place —
    frontend/reliability.py's first-token-wins race — and that call
    site speaks the first-wins / cancellation / pre-commit vocabulary,
    so the gate holds at zero findings."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R24"], \
            (rel, [x.message for x in found if x.rule == "R24"])


# -- R25: streamed window-pool claim/fill/victim discipline --------------------

R25_BAD = """
    def stage_segment(pool, key, views, lid):
        # "just stage the page" — nothing says why a stale half can't
        # be consumed or what guards the bytes coming off the tier
        pool.prefetch(key, views, lid)
        arrs, hit = pool.take(key, views, lid)
        return arrs
"""


def test_r25_flags_undisciplined_window_pool_sites():
    found = lint_source(textwrap.dedent(R25_BAD),
                        "dynamo_tpu/engine/fixture.py")
    r25 = [x for x in found if x.rule == "R25"]
    assert len(r25) == 2      # the fill AND the claim both flag
    # a driver script staging pages flags too — tools/ is in scope
    found = lint_source(textwrap.dedent(R25_BAD), "tools/fixture.py")
    assert "R25" in rules(found)
    # the victim leg flags on its own terminal
    victim = """
        def shrink(streamer, ss):
            streamer._spill_victims(ss)
    """
    found = lint_source(textwrap.dedent(victim),
                        "dynamo_tpu/engine/fixture.py")
    assert "R25" in rules(found)


def test_r25_quiet_outside_scope():
    found = lint_source(textwrap.dedent(R25_BAD), "examples/fixture.py")
    assert "R25" not in rules(found)
    found = lint_source(textwrap.dedent(R25_BAD), "tests/fixture.py")
    assert "R25" not in rules(found)


def test_r25_quiet_when_function_speaks_the_discipline():
    disciplined = """
        def stage_segment(pool, key, views, lid):
            # double buffer keyed by chained page hashes: a stale
            # prefetch never matches, and the cold views were already
            # checksum-verified at pin time (rot quarantines + only
            # the victim page recomputes)
            pool.prefetch(key, views, lid)
            arrs, hit = pool.take(key, views, lid)
            return arrs
    """
    found = lint_source(textwrap.dedent(disciplined),
                        "dynamo_tpu/engine/fixture.py")
    assert "R25" not in rules(found)
    # bare "stream"/"page" words must NOT satisfy the rule
    vague = """
        def stage_segment(pool, key, views, lid):
            # stream the page in
            pool.take(key, views, lid)
    """
    found = lint_source(textwrap.dedent(vague),
                        "dynamo_tpu/engine/fixture.py")
    assert "R25" in rules(found)


def test_r25_quiet_on_annotated_sites():
    annotated = """
        def warm_pool(pool, key, views, lid):
            # dynalint: stream-ok=offline warmup, no decode consumes this
            pool.prefetch(key, views, lid)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/engine/fixture.py")
    assert "R25" not in rules(found)


def test_r25_live_tree_window_pool_sites_disciplined():
    """The live tree touches the streamed window pool from exactly one
    module — engine/streaming.py's claim/fill/victim legs — and every
    enclosing function speaks the keyed-double-buffer / verify-on-fetch
    / checksummed-spill vocabulary, so the gate holds at zero."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R25"], \
            (rel, [x.message for x in found if x.rule == "R25"])


def test_r19_live_on_preemption_call_sites():
    """Every live preemption / victim-selection / class-ordered-dequeue
    call site references the aging/no-starvation bound or carries a
    justified annotation (engine/scheduler.py preempt paths, the
    disagg PrefillWorker consume loop, the QoS storm driver)."""
    import glob
    scoped = glob.glob(os.path.join(REPO, "dynamo_tpu", "**", "*.py"),
                       recursive=True)
    scoped += glob.glob(os.path.join(REPO, "tools", "*.py"))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R19"], \
            (rel, [x.message for x in found if x.rule == "R19"])


# -- layer 3: flow-sensitive escapes closed (flow.py) --------------------------
#
# One paired fixture per escape that docs/ANALYSIS.md used to list as a
# "Static limitation": the positive is a shape the PRE-flow lexical rule
# provably missed (the bug hides behind a name binding), the negative is
# the legitimate idiom the new recognition must keep quiet on.

def test_r7_flow_flags_timeout_variable_that_is_always_none():
    # pre-flow escape: `timeout=deadline` satisfied the lexical
    # "has a timeout kwarg" check even when the variable is None on
    # every reaching path — asyncio's wait-forever with extra steps
    leaky = """
        async def dispatch(messaging, subject, payload):
            deadline = None
            return await messaging.request(subject, payload,
                                           timeout=deadline)
    """
    found = lint_source(textwrap.dedent(leaky),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R7" in rules(found)


def test_r7_flow_quiet_when_variable_may_hold_a_budget():
    # a real constant budget through a binding: quiet
    bounded = """
        async def dispatch(messaging, subject, payload):
            t = 30.0
            return await messaging.request(subject, payload, timeout=t)
    """
    found = lint_source(textwrap.dedent(bounded),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R7" not in rules(found)
    # one path None, one path bounded: MAY hold a budget — benefit of
    # the doubt (the rule only fires on an all-paths-None proof)
    maybe = """
        async def dispatch(messaging, subject, payload, fast):
            t = None
            if fast:
                t = 5.0
            return await messaging.request(subject, payload, timeout=t)
    """
    found = lint_source(textwrap.dedent(maybe),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R7" not in rules(found)
    # parameter-fed timeout: incomplete constant set, no claim
    param = """
        async def dispatch(messaging, subject, payload, t=None):
            return await messaging.request(subject, payload, timeout=t)
    """
    found = lint_source(textwrap.dedent(param),
                        "dynamo_tpu/frontend/fixture.py")
    assert "R7" not in rules(found)


def test_r14_flow_flags_timeout_variable_that_is_always_none():
    leaky = """
        from dynamo_tpu.runtime.transports.wire import read_frame

        async def pump(reader):
            t = None
            return await read_frame(reader, timeout=t)
    """
    found = lint_source(textwrap.dedent(leaky),
                        "dynamo_tpu/runtime/transports/fixture.py")
    assert "R14" in rules(found)


def test_r14_flow_quiet_on_bound_timeout_variable():
    bounded = """
        from dynamo_tpu.runtime.transports.wire import read_frame

        async def pump(reader):
            t = 5.0
            return await read_frame(reader, timeout=t)
    """
    found = lint_source(textwrap.dedent(bounded),
                        "dynamo_tpu/runtime/transports/fixture.py")
    assert "R14" not in rules(found)


def test_r10_flow_follows_len_through_a_binding():
    # pre-flow escape: `n = len(batch)` one statement before the
    # allocation hid the data-dependent dim from the lexical
    # "len() inside the shape element" check
    leaky = """
        import numpy as np

        def _build_mixed(batch, tb):
            n = len(batch)
            tokens = np.zeros((n, tb), np.int32)
            return tokens
    """
    found = lint_source(textwrap.dedent(leaky),
                        "dynamo_tpu/engine/scheduler_fixture.py")
    assert "R10" in rules(found)


def test_r10_flow_quiet_when_len_is_laundered_through_a_bucket():
    # the binding derives from len() but passes through next_bucket():
    # admission-stable, exactly the idiom the planners use
    bucketed = """
        import numpy as np

        def _build_mixed(batch, tb, buckets):
            n = next_bucket(len(batch), buckets)
            tokens = np.zeros((n, tb), np.int32)
            return tokens
    """
    found = lint_source(textwrap.dedent(bucketed),
                        "dynamo_tpu/engine/scheduler_fixture.py")
    assert "R10" not in rules(found)


def test_r11_flow_tracks_cache_leaf_alias_into_float_math():
    # pre-flow escape: the annotated whole-page read was sanctioned,
    # but the ALIAS carried the quantized bytes into .astype(float)
    # three lines later where the lexical rule could not see them
    leaky = """
        import jax.numpy as jnp

        def leaky_alias(cache, page_table):
            # dynalint: kv-codec — whole-page move keeps representation
            k = cache["k"]
            moved = jnp.take(k, page_table, axis=2)
            cast = k.astype(jnp.float32)
            return moved, cast
    """
    found = lint_source(textwrap.dedent(leaky),
                        "dynamo_tpu/models/fixture.py")
    assert len([f for f in found if f.rule == "R11"]) == 1  # the astype
    # and through a cache-dict alias + arithmetic, same escape
    arith = """
        def mix(cache, scale):
            kv = cache
            k = kv["k"]
            return k * scale
    """
    found = lint_source(textwrap.dedent(arith),
                        "dynamo_tpu/models/fixture.py")
    assert "R11" in rules(found)


def test_r11_flow_quiet_on_representation_preserving_alias_use():
    # the alias only feeds whole-page moves / a dequantizing consumer:
    # no astype-to-float, no raw arithmetic — quiet
    neg = """
        import jax.numpy as jnp
        from dynamo_tpu.ops.kv_quant import dequantize_rows

        def codec_path(cache, page_table):
            # dynalint: kv-codec — whole-page move keeps representation
            k = cache["k"]
            g = jnp.take(k, page_table, axis=1)
            return dequantize_rows(g, None, jnp.bfloat16)
    """
    found = lint_source(textwrap.dedent(neg),
                        "dynamo_tpu/models/fixture.py")
    assert "R11" not in rules(found)
    # annotated downstream cast: the codec site moved, the annotation
    # moved with it
    annotated = """
        import jax.numpy as jnp

        def codec_cast(cache):
            # dynalint: kv-codec — capture for the dequant below
            k = cache["k"]
            # dynalint: kv-codec — dequant entry, scales applied inside
            return k.astype(jnp.float32)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/models/fixture.py")
    assert "R11" not in rules(found)


def test_r13_flow_flags_leak_despite_unrelated_try_finally():
    # pre-flow escape: the old heuristic blessed EVERY begin_span in a
    # function where SOME try/finally ended a span — this early return
    # leaks before the try is ever entered, and only the CFG sees it
    leaky = """
        from dynamo_tpu.runtime.tracing import TRACER

        async def serve_one(trace, req):
            span = TRACER.begin_span("serve", trace)
            if req.bad:
                return None          # leaks: the finally is never reached
            try:
                return await req.run()
            finally:
                TRACER.end_span(span)
    """
    assert "R13" in rules(lint(leaky))


def test_r13_flow_proves_branch_complete_and_loop_exit_endings():
    # branch-complete ending, no try/finally anywhere: the must-reach
    # proof is the only thing keeping this quiet
    branchy = """
        from dynamo_tpu.runtime.tracing import TRACER

        def run_one(trace, req):
            span = TRACER.begin_span("serve", trace)
            if req.fast:
                out = req.fast_path()
            else:
                out = req.slow_path()
            TRACER.end_span(span)
            return out
    """
    assert "R13" not in rules(lint(branchy))
    # continue inside try/finally: the back edge routes THROUGH the
    # finally, so every attempt's span still ends (the reliability
    # retry-machine shape)
    retry = """
        from dynamo_tpu.runtime.tracing import TRACER

        async def retry_loop(trace, req):
            while True:
                span = TRACER.begin_span("attempt", trace)
                try:
                    r = await req.run()
                    if r is None:
                        continue
                    return r
                finally:
                    TRACER.end_span(span)
    """
    assert "R13" not in rules(lint(retry))
    # span factory: the begin's result is returned — ownership (and the
    # end obligation) transfers to the caller
    factory = """
        from dynamo_tpu.runtime.tracing import TRACER

        def open_span(trace):
            return TRACER.begin_span("serve", trace)
    """
    assert "R13" not in rules(lint(factory))


# -- R21: await-interleaving TOCTOU (interleave.py) ----------------------------

R21_SRC = """
    async def route(self, rid, payload):
        worker = self.workers[rid]
        await self.queue.put(rid)
        return await worker.dispatch(payload)
"""


def test_r21_flags_stale_snapshot_committed_after_await():
    found = lint_source(textwrap.dedent(R21_SRC),
                        "dynamo_tpu/runtime/fixture.py")
    r21 = [f for f in found if f.rule == "R21"]
    assert len(r21) == 1
    assert "worker" in r21[0].message and "self.workers" in r21[0].message


def test_r21_quiet_outside_async_control_plane_scope():
    found = lint_source(textwrap.dedent(R21_SRC),
                        "dynamo_tpu/models/fixture.py")
    assert "R21" not in rules(found)


def test_r21_quiet_on_post_await_reread_and_fence():
    reread = """
        async def route(self, rid, payload):
            worker = self.workers[rid]
            await self.queue.put(rid)
            worker = self.workers.get(rid)   # use-time re-read
            if worker is None:
                raise KeyError(rid)
            return await worker.dispatch(payload)
    """
    found = lint_source(textwrap.dedent(reread),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R21" not in rules(found)
    fenced = """
        async def commit_pages(self, rid, pages):
            seq = self.pending[rid]
            await self._stage(pages)
            if seq.epoch != self.lease_epoch(rid):   # fence check
                raise KeyError(rid)
            return seq.commit(pages)
    """
    found = lint_source(textwrap.dedent(fenced),
                        "dynamo_tpu/disagg/fixture.py")
    assert "R21" not in rules(found)


def test_r21_quiet_on_interleave_ok_annotation():
    annotated = """
        async def route(self, rid, payload):
            worker = self.workers[rid]
            await self.queue.put(rid)
            # dynalint: interleave-ok=dispatch-revalidates-liveness-and-
            # raises-on-a-deregistered-worker
            return await worker.dispatch(payload)
    """
    found = lint_source(textwrap.dedent(annotated),
                        "dynamo_tpu/runtime/fixture.py")
    assert "R21" not in rules(found)


def test_r21_live_on_async_control_plane():
    """The R21 sweep stays fully triaged: zero unannotated stale-snapshot
    commits across runtime/, disagg/, frontend/, kv_router/ (the one
    real race it found — LocalTransferBackend's pre-staging receiver
    snapshot — is FIXED, with a regression test in test_disagg.py)."""
    import glob
    scoped = []
    for pat in ("dynamo_tpu/runtime/**/*.py", "dynamo_tpu/disagg/*.py",
                "dynamo_tpu/frontend/*.py", "dynamo_tpu/kv_router/*.py"):
        scoped.extend(glob.glob(os.path.join(REPO, pat), recursive=True))
    assert scoped
    for path in scoped:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            found = lint_source(f.read(), rel)
        assert not [x for x in found if x.rule == "R21"], \
            (rel, [x.message for x in found if x.rule == "R21"])


# -- jaxpr invariants ----------------------------------------------------------

def test_j1_flags_float64_leak():
    with jax.enable_x64(True):
        found = trace_and_audit(
            "j1pos", lambda x: jnp.asarray(np.float64(2.0)) * x,
            jnp.zeros((4,), jnp.float32))
    assert "J1" in rules(found)


def test_j1_quiet_on_f32():
    found = trace_and_audit("j1neg", lambda x: x * 2.0,
                            jnp.zeros((4,), jnp.float32))
    assert not found


def test_j2_flags_unconsumable_donation():
    found = audit_donation(
        "j2pos", lambda a, b: a * 1.0, (1,),
        jnp.zeros((4,), jnp.float32), jnp.zeros((8,), jnp.float32))
    assert rules(found) == {"J2"}


def test_j2_quiet_when_output_matches():
    found = audit_donation(
        "j2neg", lambda a, b: (a.sum(), b + 1.0), (1,),
        jnp.zeros((4,), jnp.float32), jnp.zeros((8,), jnp.float32))
    assert not found


def test_j3_flags_dead_rung_and_escape():
    from dynamo_tpu.engine.scheduler import next_bucket
    dead = audit_bucket_ladder("j3dead", (16, 32), next_bucket, max_n=8)
    assert "J3" in rules(dead)
    escape = audit_bucket_ladder("j3esc", (4,), next_bucket, max_n=8)
    assert "J3" in rules(escape)


def test_j3_quiet_on_tight_ladder():
    from dynamo_tpu.engine.scheduler import next_bucket
    assert not audit_bucket_ladder("j3neg", (4, 8), next_bucket, max_n=8)


def test_j4_flags_host_callback():
    def f(x):
        return jax.pure_callback(
            lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    assert "J4" in rules(trace_and_audit("j4pos", f,
                                         jnp.zeros((4,), jnp.float32)))


def test_j4_quiet_without_callback():
    assert not trace_and_audit("j4neg", lambda x: x + 1,
                               jnp.zeros((4,), jnp.float32))


def test_j5_flags_convert_round_trip():
    found = trace_and_audit(
        "j5pos", lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
        jnp.zeros((4,), jnp.float32))
    assert "J5" in rules(found)


def test_j5_quiet_when_intermediate_is_used():
    def f(x):
        y = x.astype(jnp.bfloat16)
        return y.astype(jnp.float32), y.sum()

    assert "J5" not in rules(trace_and_audit(
        "j5neg", f, jnp.zeros((4,), jnp.float32)))


# -- baseline mechanics --------------------------------------------------------

def test_baseline_suppresses_by_line_text_not_line_number(tmp_path):
    f1 = Finding(rule="R3", path="a.py", line=10, message="m",
                 line_text="time.sleep(1)")
    path = str(tmp_path / "b.json")
    save_baseline(path, [f1])
    moved = Finding(rule="R3", path="a.py", line=99, message="m",
                    line_text="time.sleep(1)")
    other = Finding(rule="R3", path="a.py", line=11, message="m",
                    line_text="time.sleep(2)")
    fresh = filter_baseline([moved, other], load_baseline(path))
    assert fresh == [other]


def test_baseline_budget_is_per_occurrence(tmp_path):
    f = Finding(rule="R4", path="a.py", line=1, message="m",
                line_text="except:")
    path = str(tmp_path / "b.json")
    save_baseline(path, [f])
    fresh = filter_baseline([f, f], load_baseline(path))
    assert len(fresh) == 1  # budget 1 covers one; the second is new


# -- the repo gate -------------------------------------------------------------

def test_repo_ast_lint_is_clean_vs_baseline():
    """Zero non-baseline AST findings over the whole package: this test
    IS the CI gate for new findings (the committed baseline is empty —
    the tree is clean after the r5 satellite fixes)."""
    findings = run_lint([os.path.join(REPO, "dynamo_tpu")], root=REPO)
    fresh = filter_baseline(findings, load_baseline(BASELINE))
    assert not fresh, "\n".join(f.render() for f in fresh)


def test_repo_jaxpr_audit_is_clean_vs_baseline():
    """Engine entry points (decode window, verify, prefill, paged
    attention, sampler, bucket ladder) trace clean on every invariant."""
    from dynamo_tpu.analysis import audit_engine_entry_points
    findings = audit_engine_entry_points()
    fresh = filter_baseline(findings, load_baseline(BASELINE))
    assert not fresh, "\n".join(f.render() for f in fresh)


def test_baseline_file_is_valid_json():
    with open(BASELINE) as f:
        entries = json.load(f)
    assert isinstance(entries, list)
    for e in entries:
        assert {"rule", "path", "line_text"} <= set(e)


def test_cli_exits_zero_on_clean_tree():
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dynalint.py"),
         "--no-jaxpr"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_json_round_trips_findings(tmp_path):
    """--json emits findings that reconstruct into Finding objects, and
    exit-code semantics are unchanged by the output format."""
    import subprocess
    import sys
    bad = tmp_path / "frontend"
    bad.mkdir()
    src = textwrap.dedent("""
        async def dispatch(messaging, subject, payload):
            deadline = None
            return await messaging.request(subject, payload,
                                           timeout=deadline)
    """)
    (bad / "leaky.py").write_text(src)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dynalint.py"),
         "--no-jaxpr", "--no-baseline", "--json", str(bad / "leaky.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["fresh"] == len(payload["findings"]) >= 1
    revived = [Finding(**d) for d in payload["findings"]]
    assert any(f.rule == "R7" for f in revived)
    assert all(f.line_text for f in revived)


def test_cli_changed_lints_only_the_merge_base_diff():
    """--changed scopes the lint to .py files changed vs the merge-base
    (plus untracked) and stays machine-readable with --json; on the
    current working tree it must agree with the full-tree gate (clean)."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "dynalint.py"),
         "--changed", "--json"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["fresh"] == 0
    for name in payload.get("files", []):
        assert name.endswith(".py") and ".." not in name
        assert os.path.exists(os.path.join(REPO, name))


# -- layer-3 cost: the memo and the wall-clock bound ---------------------------

def test_flow_layer_rides_the_lint_source_memo(monkeypatch):
    """Repeated passes over an unchanged file are served from the
    content-keyed memo: the flow/CFG solve happens once per (path,
    content), not once per live gate. Proven by making re-parse
    impossible and linting again."""
    from dynamo_tpu.analysis import runner
    src = textwrap.dedent(R21_SRC)
    path = "dynamo_tpu/runtime/memo_fixture.py"
    first = lint_source(src, path)
    assert (path, hash(src)) in runner._LINT_CACHE

    def boom(*a, **k):  # pragma: no cover
        raise AssertionError("memo miss: re-analyzed an unchanged file")

    monkeypatch.setattr(runner.ast, "parse", boom)
    second = lint_source(src, path)
    assert second == first
    assert second is not first  # defensive copy, not the cached list


def test_flow_layer_wall_time_is_bounded():
    """One COLD full-tree pass (memo defeated by a content salt, so
    every file re-runs all rules including the layer-3 CFG/dataflow
    solves) stays a small fraction of the 870s tier-1 budget."""
    import glob
    import time
    files = sorted(glob.glob(os.path.join(REPO, "dynamo_tpu/**/*.py"),
                             recursive=True))
    assert len(files) > 50
    t0 = time.monotonic()
    for path in files:
        rel = os.path.relpath(path, REPO)
        with open(path) as f:
            lint_source(f.read() + "\n# cold-pass salt\n", rel)
    dt = time.monotonic() - t0
    assert dt < 120.0, f"cold full-tree lint took {dt:.1f}s"
