"""Layer-2 dynalint: jaxpr invariant auditor for the jitted hot paths.

The AST layer catches source-level bug classes; this layer traces the
engine's actual jitted entry points (decode window, verify step,
prefill step, paged-attention kernels, sampler, the sharded page-slice
injection, and the pp x kv_quant pipeline forward) with abstract
bucket-shaped inputs and asserts invariants on the resulting jaxprs —
the closest a Python/JAX rebuild gets to the compile-time guarantees
NVIDIA Dynamo buys from rustc (PAPER.md §1). Tracing is cheap (no
compile, no device), so the audit runs in the tier-1 test gate.

Invariants / rule ids:

- J1  no float64 avals anywhere in the jaxpr (a silent f64 leak doubles
      HBM traffic and usually means a stray numpy scalar promoted a
      whole activation chain)
- J2  every declared donated argument is consumable: some output leaf
      matches its shape/dtype, so XLA can actually alias the buffer
      (donating the KV cache and then not returning it wastes the whole
      cache's HBM twice over)
- J3  the prefill bucket ladder is trace-tight: padding every length
      1..max_chunk onto the ladder triggers exactly len(ladder)
      retraces — no shape-driven recompiles, no dead rungs
- J4  no host callbacks (pure_callback / io_callback / debug_callback)
      inside hot jitted programs — each one is a device->host sync per
      step
- J5  no convert_element_type round-trips (x -> dtype B -> back to A
      with the intermediate unused elsewhere): a silent precision wash
      that XLA does not always elide
"""
from __future__ import annotations

import functools
from typing import Any, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from dynamo_tpu.analysis.findings import Finding


# -- jaxpr walking -------------------------------------------------------------

def _sub_jaxprs(params: dict) -> Iterable[Any]:
    for v in params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for item in vals:
            if hasattr(item, "eqns"):            # Jaxpr
                yield item
            elif hasattr(item, "jaxpr"):         # ClosedJaxpr
                yield item.jaxpr


def iter_jaxprs(jaxpr) -> Iterable[Any]:
    """Yield a jaxpr and every nested sub-jaxpr (scan/cond/pjit bodies)."""
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            stack.extend(_sub_jaxprs(eqn.params))


def iter_eqns(jaxpr) -> Iterable[Any]:
    for j in iter_jaxprs(jaxpr):
        yield from j.eqns


def _aval_dtype(var) -> Optional[Any]:
    aval = getattr(var, "aval", None)
    return getattr(aval, "dtype", None)


# -- J1 / J4 / J5: per-jaxpr scans --------------------------------------------

def audit_closed_jaxpr(entry: str, closed) -> List[Finding]:
    """Scan one traced entry point's jaxpr for J1/J4/J5 violations."""
    path = f"jaxpr:{entry}"
    findings: List[Finding] = []
    jaxpr = getattr(closed, "jaxpr", closed)
    seen_f64 = set()
    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name
        for var in eqn.outvars:
            dt = _aval_dtype(var)
            if dt is not None and str(dt) == "float64" \
                    and prim not in seen_f64:
                seen_f64.add(prim)
                findings.append(Finding(
                    rule="J1", path=path, line=0,
                    message=f"float64 aval produced by `{prim}` — a "
                            "silent f64 leak doubles the chain's HBM "
                            "traffic",
                    hint="find the numpy scalar / dtype-less constant "
                         "that promoted the chain; cast it explicitly",
                    line_text=f"{prim} -> float64"))
        if "callback" in prim or prim == "outside_call":
            findings.append(Finding(
                rule="J4", path=path, line=0,
                message=f"host callback `{prim}` inside a hot jitted "
                        "program — a device->host sync every step",
                hint="move the host work to the step boundary or a "
                     "background thread",
                line_text=prim))
    # J5: convert_element_type chains that round-trip, per jaxpr scope
    for j in iter_jaxprs(jaxpr):
        producers = {}
        uses: dict = {}
        for eqn in j.eqns:
            for var in eqn.invars:
                # skip Literals (unhashable, and never cast chains)
                if hasattr(var, "aval") and not hasattr(var, "val"):
                    uses[var] = uses.get(var, 0) + 1
            for var in eqn.outvars:
                producers[var] = eqn
        for eqn in j.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = eqn.invars[0]
            prod = producers.get(src)
            if prod is None \
                    or prod.primitive.name != "convert_element_type":
                continue
            orig = _aval_dtype(prod.invars[0])
            final = _aval_dtype(eqn.outvars[0])
            if orig is not None and orig == final and uses.get(src) == 1:
                mid = _aval_dtype(src)
                findings.append(Finding(
                    rule="J5", path=path, line=0,
                    message=f"convert_element_type round-trip "
                            f"{orig} -> {mid} -> {final} with the "
                            "intermediate unused elsewhere — a silent "
                            "precision wash",
                    hint="drop the paired casts or keep the compute in "
                         "the intermediate dtype on purpose (and say so)",
                    line_text=f"{orig}->{mid}->{final}"))
    return findings


def trace_and_audit(entry: str, fn, *args, **kwargs) -> List[Finding]:
    """jax.make_jaxpr a callable on example args and scan its jaxpr."""
    try:
        closed = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    except Exception as e:  # noqa: BLE001 — a trace failure IS a finding
        return [Finding(
            rule="J0", path=f"jaxpr:{entry}", line=0,
            message=f"entry point failed to trace: {type(e).__name__}: "
                    f"{e}",
            line_text="trace-failure")]
    return audit_closed_jaxpr(entry, closed)


# -- J2: donation consumability -----------------------------------------------

def audit_donation(entry: str, fn, donate_argnums: Sequence[int],
                   *args, **kwargs) -> List[Finding]:
    """Declared donations must be consumable: every donated input leaf
    needs a distinct shape/dtype-matched output leaf for XLA to alias."""
    out_shape = jax.eval_shape(functools.partial(fn, **kwargs), *args)
    out_leaves = [(tuple(leaf.shape), str(leaf.dtype))
                  for leaf in jax.tree_util.tree_leaves(out_shape)
                  if hasattr(leaf, "shape")]
    findings: List[Finding] = []
    for argnum in donate_argnums:
        pool = list(out_leaves)
        for leaf in jax.tree_util.tree_leaves(args[argnum]):
            if not hasattr(leaf, "shape"):
                continue
            sig = (tuple(leaf.shape), str(leaf.dtype))
            if sig in pool:
                pool.remove(sig)
            else:
                findings.append(Finding(
                    rule="J2", path=f"jaxpr:{entry}", line=0,
                    message=f"donated arg {argnum} leaf "
                            f"{sig[0]}/{sig[1]} has no matching output "
                            "buffer — the donation can never be "
                            "consumed and the buffer is dead weight",
                    hint="return the updated buffer (in-place .at[] "
                         "update) or stop donating it",
                    line_text=f"arg{argnum}:{sig[0]}:{sig[1]}"))
    return findings


# -- J3: bucket-ladder trace tightness ----------------------------------------

def audit_bucket_ladder(entry: str, buckets: Sequence[int],
                        next_bucket, max_n: Optional[int] = None
                        ) -> List[Finding]:
    """Pad every length 1..max onto the ladder through `next_bucket` and
    count actual jit retraces: exactly len(buckets) distinct programs
    means no shape-driven recompiles and no dead rungs."""
    max_n = max_n or max(buckets)
    traces: List[Tuple[int, ...]] = []

    @jax.jit
    def probe(x):
        traces.append(x.shape)
        return x.sum()

    findings: List[Finding] = []
    for n in range(1, max_n + 1):
        try:
            b = next_bucket(n, buckets)
        except ValueError as e:
            findings.append(Finding(
                rule="J3", path=f"jaxpr:{entry}", line=0,
                message=f"length {n} escapes the bucket ladder "
                        f"{tuple(buckets)}: {e}",
                hint="the ladder's top rung must cover the maximum "
                     "schedulable length",
                line_text=f"escape:{n}"))
            continue
        probe(jnp.zeros((b,), jnp.float32))
    n_traces, n_rungs = len(traces), len(set(buckets))
    if not findings and n_traces != n_rungs:
        kind = ("shape-driven recompiles"
                if n_traces > n_rungs else "dead rungs (wasted compiles "
                "at first use)")
        findings.append(Finding(
            rule="J3", path=f"jaxpr:{entry}", line=0,
            message=f"bucket ladder {tuple(buckets)} produced "
                    f"{n_traces} retraces for lengths 1..{max_n}, "
                    f"expected {n_rungs} — {kind}",
            hint="next_bucket must map every length onto exactly the "
                 "configured rungs",
            line_text=f"retraces:{n_traces}!={n_rungs}"))
    return findings


# -- the engine audit: trace the real entry points ----------------------------

def _zeros_like_shape(tree):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), tree)


def audit_engine_entry_points() -> List[Finding]:
    """Trace the serving hot paths on a tiny abstract config and run
    every invariant. CPU-safe: nothing compiles or touches a device
    beyond trivial zeros allocation."""
    from dynamo_tpu.engine.config import ModelConfig
    from dynamo_tpu.engine.engine import (
        _engine_decode_window, _engine_step, _engine_verify_step,
    )
    from dynamo_tpu.engine.sampler import sample_logits
    from dynamo_tpu.engine.scheduler import next_bucket
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops.paged_attention import decode_paged_attention

    cfg = ModelConfig(name="dynalint-audit", dtype="float32",
                      vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_layers=2, num_heads=4,
                      num_kv_heads=2, head_dim=16, max_model_len=64,
                      decode_kernel="off")
    s, pb, ps, pages, nw, kp1, tq = 2, 4, 8, 16, 2, 3, 8
    eos = (2,)

    params = _zeros_like_shape(jax.eval_shape(
        functools.partial(llama.init_params, cfg=cfg),
        jax.random.PRNGKey(0)))
    cache = _zeros_like_shape(jax.eval_shape(functools.partial(
        llama.init_cache, cfg, num_pages=pages, page_size=ps)))

    i32 = functools.partial(jnp.zeros, dtype=jnp.int32)
    f32 = functools.partial(jnp.zeros, dtype=jnp.float32)

    findings: List[Finding] = []

    decode_fn = functools.partial(
        _engine_decode_window, cfg, eos, None, nw, ps, False, False, True)
    decode_args = (params, cache, i32((s,)), i32((s,)), i32((s, pb)),
                   i32((s, pb)), i32((s,)), f32((s,)), i32((s,)),
                   jnp.ones((s,), jnp.float32), i32((s,)), i32((s,)),
                   i32((s,)), jnp.ones((s,), bool), i32((s, 1)))
    findings += trace_and_audit("engine_decode_window", decode_fn,
                                *decode_args)
    findings += audit_donation("engine_decode_window", decode_fn, (1,),
                               *decode_args)

    verify_fn = functools.partial(_engine_verify_step, cfg, eos, None,
                                  None, None)
    verify_args = (params, cache, i32((s, kp1)), i32((s, kp1)),
                   i32((s, pb)), i32((s,)), i32((s, kp1)), i32((s,)),
                   i32((s,)))
    findings += trace_and_audit("engine_verify_step", verify_fn,
                                *verify_args)
    findings += audit_donation("engine_verify_step", verify_fn, (1,),
                               *verify_args)

    prefill_fn = functools.partial(_engine_step, cfg, eos, None, None,
                                   False, False, False, None)
    prefill_args = (params, cache, i32((s, tq)), i32((s, tq)),
                    i32((s, pb)), i32((s,)), i32((s, tq)), i32((s,)),
                    f32((s,)), i32((s,)), jnp.ones((s,), jnp.float32),
                    i32((s,)), i32((s,)), i32((s,)))
    findings += trace_and_audit("engine_prefill_step", prefill_fn,
                                *prefill_args)
    findings += audit_donation("engine_prefill_step", prefill_fn, (1,),
                               *prefill_args)

    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    h = cfg.num_heads
    findings += trace_and_audit(
        "paged_attention_decode", decode_paged_attention,
        f32((s, h, hd)), f32((hkv, pages, ps, hd)),
        f32((hkv, pages, ps, hd)), i32((s, pb)), jnp.ones((s,), jnp.int32),
        interpret=True)

    def sampler_entry(logits, temp, top_k, top_p, seeds, ctr, min_toks):
        return sample_logits(logits, eos, temp, top_k, top_p, seeds,
                             ctr, min_toks)

    findings += trace_and_audit(
        "sampler", sampler_entry,
        f32((s, cfg.vocab_size)), f32((s,)), i32((s,)),
        jnp.ones((s,), jnp.float32), i32((s,)), i32((s,)), i32((s,)))

    # sharded parallel KV injection (disagg data plane): one compiled
    # program per shard-plan entry — static slice bounds, donated cache,
    # page ids as the only data. Audited on a real kv_shard_layout entry
    # so the slice/donation contract can't drift from the planner.
    from dynamo_tpu.engine.engine import _inject_pages_slice
    from dynamo_tpu.parallel.mesh import kv_shard_layout, make_mesh

    nb = 3
    plan = kv_shard_layout(cfg.num_layers, cfg.num_kv_heads,
                           n_streams=cfg.num_kv_heads)
    sl = plan[0]
    count = sl[0][2]
    slice_pages = {
        "k": f32((cfg.num_layers, count, nb, ps, cfg.head_dim)),
        "v": f32((cfg.num_layers, count, nb, ps, cfg.head_dim)),
    }
    inject_fn = functools.partial(_inject_pages_slice,
                                  slices=tuple(tuple(x) for x in sl))
    inject_args = (cache, i32((nb,)), slice_pages)
    findings += trace_and_audit("inject_pages_shard", inject_fn,
                                *inject_args)
    findings += audit_donation("inject_pages_shard", inject_fn, (0,),
                               *inject_args)

    # pp x kv_quant stage scan: the pipeline forward threads int8 value
    # shards AND their paired f32 scale stacks through the stage scan
    # (models/pp.py _stage -> write_kv_pages_quant). pp adapts to the
    # device count so the audit also runs on a single-device CLI
    # invocation (tier-1 runs with 8 virtual CPU devices).
    from dynamo_tpu.models.llama import AttnMetadata
    from dynamo_tpu.models.pp import pp_forward

    cfg_q = ModelConfig(name="dynalint-audit-ppq", dtype="float32",
                        vocab_size=128, hidden_size=64,
                        intermediate_size=128, num_layers=2, num_heads=4,
                        num_kv_heads=2, head_dim=16, max_model_len=64,
                        decode_kernel="off", kv_quant="int8")
    pp = 2 if len(jax.devices()) >= 2 else 1
    mesh = make_mesh(pp=pp, devices=jax.devices()[:pp])
    params_q = _zeros_like_shape(jax.eval_shape(
        functools.partial(llama.init_params, cfg=cfg_q),
        jax.random.PRNGKey(0)))
    cache_q = _zeros_like_shape(jax.eval_shape(functools.partial(
        llama.init_cache, cfg_q, num_pages=pages, page_size=ps)))
    meta = AttnMetadata(positions=i32((s, tq)), page_table=i32((s, pb)),
                        kv_lens=i32((s,)), write_idx=i32((s, tq)))
    tokens = i32((s, tq))
    findings += trace_and_audit(
        "pp_forward_kv_quant",
        lambda p, c: pp_forward(p, cfg_q, tokens, c, meta, mesh),
        params_q, cache_q)

    findings += audit_bucket_ladder(
        "prefill_bucket_ladder", (8, 16, 32), next_bucket)
    return findings
