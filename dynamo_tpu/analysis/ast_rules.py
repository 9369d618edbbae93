"""Layer-1 dynalint: AST rules distilled from this repo's bug history.

Each rule is a function (tree, lines, path) -> List[Finding] registered
in RULES. Rules are deliberately project-specific pattern matchers, not
general-purpose lints: every one encodes a bug class that actually cost
a debug round here (ADVICE.md r1-r5), the way NVIDIA Dynamo leans on
clippy for the classes Rust can express. False positives are expected
to be rare and are handled by an inline `# dynalint: disable=Rn`
annotation on the flagged line (with a justification) or by the
checked-in baseline (findings.py).

Rule ids (docs/ANALYSIS.md has the long-form description of each):

- R1  unguarded token-id flow into embedding/vocab-sized gathers
- R2  Pallas decode kernel contracting against K/V without stale-tail
      masking (vpos/kv_len zeroing)
- R3  blocking call inside `async def`
- R4  bare/BaseException handler that can swallow CancelledError
- R5  mutation of a dict/list while iterating it
- R6  host-sync call in a file marked `# dynalint: hot-path`
- R7  unbounded await on a control-plane/transport round trip in the
      serving layers (transports/, frontend/, disagg/) — a missing
      timeout= kwarg, a literal timeout=None, or (layer 3, flow.py) a
      timeout variable that constant-propagates to None on every path
- R8  blocking device sync (jax.device_get / .block_until_ready() /
      np.asarray(<device array>)) inside a `# dynalint: hot-path-begin`
      .. `hot-path-end` region without an explicit
      `# dynalint: sync-point` justification
- R9  `except Exception:` in the serving layers (runtime/, disagg/,
      frontend/) whose body only passes or logs-and-continues, without a
      `# dynalint: swallow-ok=<reason>` annotation
- R10 schedule()-reachable plan builders allocating per-step arrays
      with an unbucketed (data-dependent `len(...)`) leading dim — every
      distinct shape mints a new compiled XLA program, so an admission-
      dependent dim recompiles the serving loop per arrival — without a
      `# dynalint: bucketed` annotation; layer 3 (flow.py) follows
      `n = len(batch)` bindings into the dim through reaching defs,
      and a value routed through next_bucket()/pow2_buckets()/
      page_bucket_ladder() is admission-stable by construction
- R11 raw KV-cache leaf access (`cache["k"]` / `cache["v"]` / the scale
      leaves) in model/ops/engine-step code without a
      `# dynalint: kv-codec` annotation — with kv_quant the leaves hold
      int8 bytes + scales, and code that indexes them directly (or
      `.astype`s them to a float) silently treats quantized bytes as
      values; every access must go through (or knowingly feed) the
      ops/kv_quant.py codec. Layer 3 (flow.py) tracks aliases: a
      `kv = cache` dict copy indexed later, and a `k = cache["k"]`
      value-leaf alias feeding downstream `.astype(<float>)` or
      arithmetic, are flagged at the consuming site
- R12 control-plane retry loops (watch pumps, heartbeat/keepalive
      loops, lease renewal, scrape loops) that survive failures —
      a `while` loop with a non-reraising exception handler around a
      control-plane call — without backoff+jitter (no name containing
      "backoff" in the loop) and without a
      `# dynalint: backoff-ok=<reason>` annotation; at fleet scale an
      un-jittered retry loop re-synchronizes hundreds of workers into
      thundering-herd waves against the discovery store
- R13 tracing span lifecycle (runtime/tracing.py): (a) a manually-begun
      span (`begin_span`) must be ended on every path — `with` form, a
      try/finally containing `end_span`/`.finish()`, or a layer-3 CFG
      proof that every path from the binding reaches an end (flow.py
      must-reach analysis; a begin whose result is immediately returned
      transfers ownership to the caller) — else early exits leak the
      span; (b) span-RECORDING calls inside
      `# dynalint: hot-path-begin/end` regions must use the deferred
      recorder (`defer_phase`, what PhaseTimer routes through) instead
      of allocating span objects between device dispatches, and a bare
      profiler annotation (`TraceAnnotation`) there is a finding too:
      `PhaseTimer.phase` carries the annotation, one call site for the
      timer, the tracer and the profiler; escape hatch
      `# dynalint: span-ok=<reason>`
- R14 unbounded raw stream IO on the data/control wire (disagg/,
      runtime/transports/): an awaited `read_frame` / `readexactly` /
      `readuntil` / `readline` / `drain` with no effective `timeout=`
      kwarg (missing, literal None, or constant-propagated None —
      layer 3), no enclosing `asyncio.wait_for` in the same await
      expression, and no
      `# dynalint: unbounded-io-ok=<reason>` annotation within three
      lines above. R7 bounds the higher-level round trips; R14 pins the
      raw socket ops under them — a half-open peer or a receiver that
      stops reading wedges exactly these awaits (the pre-fix
      RemoteTransferBackend ack read is the type specimen: a decode
      worker restart left the sender blocked forever on a dead socket)
- R15 metric registration contract (dynamo_tpu/ package): every
      `registry.counter/gauge/histogram(name, help, ...)` must carry
      non-empty help text AND its family must appear in the
      docs/OBSERVABILITY.md metric catalog (f-string names resolve by
      literal fragments); an undocumented family is invisible to the
      runbooks and exempt from the catalog completeness test — escape
      hatch `# dynalint: metric-doc-ok=<reason>`
- R16 transfer-cost fallback contract (dynamo_tpu/ + tools/): any
      consumer of the TransferCostModel's scalar queries
      (`estimate_s(...)`, `bandwidth_bytes_per_s(...)`, or a
      `.estimate(...)` on a cost-model receiver) must visibly handle
      the no-data branch — the enclosing function references the
      cold/measured/frozen/degraded/default/median vocabulary — or
      carry `# dynalint: cost-fallback-ok=<reason>`. A cold or
      degraded-stale estimate silently treated as a measurement is
      exactly how a router over-commits to an unmeasured link
- R17 actuation pacing contract (dynamo_tpu/ + tools/): a call to the
      fleet actuators — `mark_draining(...)`, `set_role(...)`,
      `re_role(...)`, `re_register(...)`, or `.drain(...)` on a
      worker/endpoint/served/instance receiver — placed inside a loop
      or a controller tick (a function named *tick*/*actuate*/
      *controller*/*rebalance*) must visibly engage pacing — the
      enclosing function references a cooldown/hysteresis/backoff/
      jitter object — or carry `# dynalint: actuation-ok=<reason>`.
      An unpaced actuation loop is a fleet-drainer: a controller that
      re-roles on every tick of a bad sensor mass-drains the fleet
      faster than any storm (runtime/autoscaler.py owns the sanctioned
      Cooldown/Hysteresis objects)
- R18 shared-pool verification contract (dynamo_tpu/ + tools/): any
      shared-KV-pool data-path call — `publish`/`fetch`/`note_source`/
      a `*pool*claim*` on a pool-shaped receiver, or
      `prefetch_pool_pages(...)` — must sit in a function that visibly
      references the checksum-verification story (checksum/verify/
      integrity/quarantine vocabulary) or carry
      `# dynalint: pool-verify-ok=<reason>`. Pool pages cross worker
      boundaries content-addressed; a call site that moves them without
      stating where the capture checksum is verified is exactly where a
      refactor can silently drop verify-on-fetch and launder rotten
      bytes into a device cache (engine/kv_pool.py owns the contract)
- R19 starvation-bound contract (dynamo_tpu/ + tools/): any
      preemption / victim-selection / class-ordered-dequeue call —
      `_preempt_one(...)`, `_preempt_for(...)` / `preempt_for(...)`,
      `select_victim(...)`, or `dequeue_leased(...)` — must sit in a
      function that visibly references the aging / no-starvation bound
      (aging|starv vocabulary — the QosPolicy.aging_limit guarantee
      every class-conscious consumer shares, runtime/qos.py) or carry
      `# dynalint: starvation-ok=<reason>`. A preemption or
      priority-ordered dequeue whose author can't point at the bound
      is exactly where a refactor silently turns weighted fairness
      into a starvation engine: the high class wins every contest and
      the batch tenant never completes
- R20 min-frontier aggregation contract (dynamo_tpu/ + tools/): any
      consumer of a committed transfer frontier — `stream_frontier(...)`
      / `committed_frontier(...)`, or the fate-deciding call sites that
      consume it (`salvage_remote(...)`, `preactivate_remote(...)`,
      `poll_overlap_gates(...)`) — must sit in a function that visibly
      references the min-over-streams aggregation (min/aggregat/
      straggler vocabulary — sharded parallel transfer commits each
      (shard, host) stream independently, and a page is only usable
      once EVERY stream committed it) or carry
      `# dynalint: frontier-ok=<reason>`. A frontier consumer that
      can't point at the min is exactly where a refactor silently
      trusts ONE stream's frontier — and salvage then charges pages
      whose sibling slices never landed, decoding garbage
      (disagg/remote_transfer.py owns the aggregation)
- R21 await-interleaving TOCTOU (layer 3, interleave.py): in any
      `async def` under runtime/, disagg/, frontend/, kv_router/, a
      name bound to shared state (`self.X`, `self.X[...]`, a module
      UPPERCASE registry) before an `await` and consumed after it by a
      fate-deciding call (dispatch/generate, inject*/salvage, commit*,
      schedule, deregister/remove_*, resolve*) without revalidation —
      a re-read or membership guard mentioning the captured root, or
      an epoch/frontier/fence/generation/corpse/alive/lease check —
      is the corpse-routing race class of PRs 7-15 mechanized; escape
      hatch `# dynalint: interleave-ok=<where revalidation lives>`
- R22 placement-epoch contract (dynamo_tpu/ + tools/): any consumer of
      a placement result — `owners_for(...)`, `ring.lookup(...)`, or
      the pool-host resolution calls (`live_hosts(...)`,
      `owner_hosts(...)`) — must sit in a function that visibly
      references the ownership-epoch discipline (epoch|stale|fence|
      re-resolve|watch|replica|rebalance vocabulary — receiver names
      like `ring.`/`membership.` alone do NOT count; the HashRing
      bumps its epoch on every join/leave, and a placement answer is
      only valid under the epoch it was computed at) or carry
      `# dynalint: ring-ok=<reason>`. A placement consumer that can't
      point at the epoch is exactly where a refactor caches an owner
      list across a membership change and writes to (or fetches from)
      hosts that no longer own the key — the zombie-sender class of
      bug, one layer down (runtime/placement.py is the placement layer
      itself and is exempt, like ops/kv_quant.py for R11)
- R23 one decode kernel (dynamo_tpu/ + tools/): constructing a decode
      attention `pl.pallas_call(...)` anywhere outside the unified
      dispatcher (ops/paged_attention.py owns THE ragged kernel; the
      frozen legacy copies live in ops/paged_attention_oracle.py as
      test oracles) must carry `# dynalint: kernel-ok=<reason>` within
      three lines above. PR 18 collapsed three decode kernels into one
      ragged kernel precisely because per-call-site kernel forks drift
      — a fork skips the stale-tail zeroing (R2) or the int8
      scale-folding and decodes garbage only on the geometry the fork
      serves. Any new direct construction is either a test oracle
      (annotate it) or a regression
- R24 hedged-dispatch exactness (dynamo_tpu/ + tools/): any call that
      dispatches a hedge attempt (`_start_hedge(...)`,
      `start_hedge(...)`, `dispatch_hedge(...)`, `hedge_dispatch(...)`)
      must sit in a function that visibly references the
      first-wins / loser-cancellation / pre-commit discipline
      (first-wins|cancel|abandon|loser|pre-commit vocabulary) or carry
      `# dynalint: hedge-ok=<reason>`. A hedge is only exact BEFORE
      the first token commits: a call site that can't point at the
      race discipline is exactly where a refactor fires a hedge after
      commit — duplicating tokens the client already consumed — or
      leaks the losing stream (frontend/reliability.py owns the
      reference race; its call site speaks the vocabulary and stays
      in scope, so a second undisciplined site still flags)
- R25 streamed window-pool claim/fill/victim discipline (dynamo_tpu/ +
      tools/): any call that claims, fills, or spills a streamed
      window-pool page (`pool.take(...)`, `pool.prefetch(...)`,
      `_assemble(...)`, `_pin_cold(...)`, `_spill_victims(...)`) must
      sit in a function that visibly references the keyed-double-buffer
      / verify-on-fetch / checksummed-spill discipline
      (double-buffer|checksum|chained-hash|quarantine|verify
      vocabulary) or carry `# dynalint: stream-ok=<reason>`. Streamed
      decode beyond HBM is only exact while a stale prefetch can never
      be consumed (halves keyed by chained page hashes), rot
      quarantines + recomputes only the victim page, and spills ride
      the checksummed offload leg — a site that can't point at those
      rules is where a refactor consumes a stale half or spills an
      unverifiable page (engine/streaming.py owns the reference loop;
      its sites speak the vocabulary and stay in scope)
"""
from __future__ import annotations

import ast
import re
from typing import Callable, Dict, List, Optional

from dynamo_tpu.analysis.findings import Finding
from dynamo_tpu.analysis.flow import header_exprs, module_flow

RULES: Dict[str, Callable] = {}


def rule(rid: str):
    def deco(fn):
        RULES[rid] = fn
        return fn
    return deco


def _unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - very old ASTs only
        return ast.dump(node)


def _line(lines: List[str], lineno: int) -> str:
    return lines[lineno - 1].strip() if 0 < lineno <= len(lines) else ""


def _finding(rid: str, path: str, lines: List[str], node: ast.AST,
             message: str, hint: str = "") -> Finding:
    return Finding(rule=rid, path=path, line=node.lineno, message=message,
                   hint=hint, line_text=_line(lines, node.lineno))


def _call_name(node: ast.Call) -> str:
    """Dotted name of the called expression ('' when not a plain name)."""
    f = node.func
    parts: List[str] = []
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
        return ".".join(reversed(parts))
    return ""


def _is_id_index(idx: ast.expr) -> bool:
    """True when a subscript index looks like a token-id array (carries a
    Name) rather than dimension plumbing (slices, None/... axis ops)."""
    has_name = False
    for n in ast.walk(idx):
        if isinstance(n, ast.Slice):
            return False
        if isinstance(n, ast.Constant) and (n.value is None
                                            or n.value is Ellipsis):
            return False
        if isinstance(n, ast.Name):
            has_name = True
    return has_name


# -- R1: unguarded vocab gathers ----------------------------------------------

# tables whose minor-0 axis is vocab-sized: an out-of-bounds take fills
# (silently, on TPU/jnp) instead of raising — the NaN-cascade class
# (spec.py salt-id bug, ADVICE r5 high)
_EMBED_RE = re.compile(r"embed|wte|tok_table|vocab_table|lm_head", re.I)
_GUARD_RE = re.compile(r"\bclip\b|\bminimum\b|\bmod\b|%")
_PROPOSE_RE = re.compile(r"propose|_drafts\b|draft_tokens")
_VOCAB_RE = re.compile(r"vocab", re.I)


@rule("R1")
def r1_unguarded_vocab_gather(tree: ast.AST, lines: List[str],
                              path: str) -> List[Finding]:
    out: List[Finding] = []
    # pattern a: jnp.take / subscript into an embedding-named table with an
    # index expression that carries no clamp
    for node in ast.walk(tree):
        table = idx = None
        if isinstance(node, ast.Call) and _call_name(node).endswith("take") \
                and len(node.args) >= 2:
            table, idx = node.args[0], node.args[1]
        elif isinstance(node, ast.Subscript) \
                and not isinstance(node.slice, (ast.Constant, ast.Slice)) \
                and _is_id_index(node.slice):
            table, idx = node.value, node.slice
        if table is None:
            continue
        if not _EMBED_RE.search(_unparse(table)):
            continue
        if _GUARD_RE.search(_unparse(idx)):
            continue
        out.append(_finding(
            "R1", path, lines, node,
            f"gather into vocab-sized table `{_unparse(table)}` with "
            f"unclamped index `{_unparse(idx)}` — an out-of-vocab id "
            "becomes NaN silently (jnp.take fills OOB reads)",
            "clip the ids to [0, vocab) or validate them before the "
            "gather (engine._validate_prompt is the admission-time "
            "equivalent)"))
    # pattern b: draft/proposal functions that return token ids scanned
    # from raw sequence history without ever consulting the vocab bound —
    # those ids feed the verify forward's embedding take verbatim
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not _PROPOSE_RE.search(node.name):
            continue
        arg_names = {a.arg for a in node.args.args}
        reads_history = "tokens" in arg_names or "token_ids" in arg_names \
            or any(isinstance(n, ast.Attribute) and n.attr == "all_tokens"
                   for n in ast.walk(node))
        if not reads_history:
            continue
        body_src = _unparse(node)
        if _VOCAB_RE.search(body_src) or "clip(" in body_src:
            continue
        out.append(_finding(
            "R1", path, lines, node,
            f"proposal function `{node.name}` returns token ids drawn "
            "from sequence history without an in-vocab guard — history "
            "may hold multimodal salt ids far outside the vocab",
            "truncate the proposal at the first id outside "
            "[0, vocab_size) before returning it"))
    return out


# -- R2: Pallas decode kernels missing stale-tail K/V zeroing -----------------

_KERNEL_RE = re.compile(r"^_(ragged_)?decode_kernel")
_BUF_RE = re.compile(r"\b[kv]_buf\b")


@rule("R2")
def r2_kernel_stale_tail(tree: ast.AST, lines: List[str],
                         path: str) -> List[Finding]:
    out: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef) \
                or not _KERNEL_RE.search(fn.name):
            continue
        # packed kernels contract over all 128 lanes, so a non-finite K
        # lane in a NEIGHBOURING token's segment poisons a valid score
        # (0 * NaN); they need K zeroed too, not just V
        packed = any(a.arg == "pack" for a in fn.args.args)
        loads: Dict[str, List[int]] = {}    # name -> load linenos
        wheres: Dict[str, List[int]] = {}   # name -> where-rebind linenos
        dot_uses: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                src = _unparse(node.value)
                if _BUF_RE.search(src) and "where" not in src:
                    loads.setdefault(name, []).append(node.lineno)
                elif "where" in src and re.search(
                        rf"\b{re.escape(name)}\b", src):
                    wheres.setdefault(name, []).append(node.lineno)
            if isinstance(node, ast.Call) \
                    and _call_name(node).endswith("dot_general"):
                for arg in node.args:
                    for n in ast.walk(arg):
                        if isinstance(n, ast.Name):
                            dot_uses.setdefault(n.id, []).append(node.lineno)
        for name, load_lns in loads.items():
            from_k = any("k_buf" in _line(lines, ln) for ln in load_lns)
            if from_k and not packed:
                # unpacked kernels mask K's scores with NEG_INF past
                # kv_len instead; lanes never mix tokens there
                continue
            for ln in load_lns:
                uses = [u for u in dot_uses.get(name, []) if u > ln]
                if not uses:
                    continue
                first_use = min(uses)
                if any(ln < w < first_use
                       for w in wheres.get(name, [])):
                    continue
                out.append(Finding(
                    rule="R2", path=path, line=ln,
                    message=(
                        f"`{fn.name}` contracts `{name}` (loaded from a "
                        "K/V page buffer) without zeroing rows past the "
                        "valid length — recycled-page tails poison the "
                        "accumulator (0 * NaN = NaN)"),
                    hint=("mask with jnp.where(vpos < kv_len, x, 0.0) "
                          "before the dot_general, like "
                          "_decode_kernel_packed"),
                    line_text=_line(lines, ln)))
    return out


# -- R3: blocking calls on async paths ----------------------------------------

_BLOCKING_EXACT = {
    "time.sleep", "os.system", "socket.create_connection",
    "urllib.request.urlopen",
}
_BLOCKING_PREFIX = ("subprocess.", "requests.")


def _visit_async_body(fn: ast.AsyncFunctionDef):
    """Yield nodes in an async function's own execution scope (skipping
    nested function/class definitions, which run on their own terms)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


@rule("R3")
def r3_blocking_in_async(tree: ast.AST, lines: List[str],
                         path: str) -> List[Finding]:
    out: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        for node in _visit_async_body(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in _BLOCKING_EXACT \
                    or name.startswith(_BLOCKING_PREFIX):
                out.append(_finding(
                    "R3", path, lines, node,
                    f"blocking call `{name}` inside `async def "
                    f"{fn.name}` stalls the whole event loop",
                    "await an async equivalent (asyncio.sleep, "
                    "create_subprocess_exec) or push it to a thread "
                    "(asyncio.to_thread / run_in_executor)"))
    return out


# -- R4: handlers that can swallow CancelledError -----------------------------

def _handler_reraises(handler: ast.ExceptHandler) -> bool:
    return any(isinstance(n, ast.Raise) and n.exc is None
               for n in ast.walk(handler))


def _catches_base(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True
    types = handler.type.elts if isinstance(handler.type, ast.Tuple) \
        else [handler.type]
    return any(_unparse(t).endswith("BaseException") for t in types)


@rule("R4")
def r4_swallows_cancellation(tree: ast.AST, lines: List[str],
                             path: str) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if _catches_base(node) and not _handler_reraises(node):
            what = "bare `except:`" if node.type is None \
                else f"`except {_unparse(node.type)}`"
            out.append(_finding(
                "R4", path, lines, node,
                f"{what} swallows asyncio.CancelledError — a cancelled "
                "task keeps running and cancellation deadlocks",
                "catch Exception instead, or re-raise: "
                "`except BaseException: cleanup(); raise`"))
    return out


# -- R5: mutating a container while iterating it ------------------------------

_MUTATORS = {"pop", "popitem", "clear", "remove", "insert", "update",
             "append", "appendleft", "extend"}


def _iter_root(node: ast.expr) -> Optional[str]:
    """Name of the container a `for` iterates directly, if any: `x`,
    `x.keys()/.values()/.items()`. Snapshot wrappers (list(x), tuple(x),
    sorted(x)) return None — they are the sanctioned fix."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
            and node.func.attr in ("keys", "values", "items") \
            and isinstance(node.func.value, ast.Name):
        return node.func.value.id
    return None


@rule("R5")
def r5_mutate_while_iterating(tree: ast.AST, lines: List[str],
                              path: str) -> List[Finding]:
    out: List[Finding] = []
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.AsyncFor)):
            continue
        root = _iter_root(loop.iter)
        if root is None:
            continue
        for node in ast.walk(loop):
            bad = None
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and isinstance(node.func.value, ast.Name) \
                    and node.func.value.id == root \
                    and node.func.attr in _MUTATORS:
                bad = f"{root}.{node.func.attr}(...)"
            elif isinstance(node, ast.Delete):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Subscript) \
                            and isinstance(tgt.value, ast.Name) \
                            and tgt.value.id == root:
                        bad = f"del {root}[...]"
            if bad:
                out.append(_finding(
                    "R5", path, lines, node,
                    f"`{bad}` mutates `{root}` while the `for` at line "
                    f"{loop.lineno} iterates it — RuntimeError on "
                    "dicts, skipped/repeated elements on lists",
                    f"iterate a snapshot: `for ... in list({root}):`"))
    return out


# -- R6: host syncs in hot-path files -----------------------------------------

# file-level marker only: must NOT match the R8 region markers
# (hot-path-begin / hot-path-end), which scope a REGION, not the file
HOT_PATH_RE = re.compile(r"#\s*dynalint:\s*hot-path(?![-\w])")
_SYNC_ATTRS = {"item", "block_until_ready"}
_SYNC_CALLS = {"jax.device_get", "device_get"}


@rule("R6")
def r6_host_sync_in_hot_path(tree: ast.AST, lines: List[str],
                             path: str) -> List[Finding]:
    if not any(HOT_PATH_RE.search(line) for line in lines):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        sync = None
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _SYNC_ATTRS:
            sync = f".{node.func.attr}()"
        elif name in _SYNC_CALLS:
            sync = f"{name}()"
        elif name == "float" and node.args \
                and not isinstance(node.args[0], ast.Constant):
            sync = "float()"
        if sync:
            out.append(_finding(
                "R6", path, lines, node,
                f"host sync `{sync}` in a hot-path file — blocks "
                "dispatch until the device result is ready",
                "keep values on device; move host reads to the step "
                "boundary (one batched device_get per step)"))
    return out


# -- R7: unbounded control-plane/transport awaits in serving layers -----------

# Only these directories are in scope: the layers whose awaits sit between
# a client request and a remote peer, where an unbounded wait on a dead
# peer wedges the whole serving path (the reliability layer's failure
# model, docs/RESILIENCE.md). Engine/device code is exempt — device steps
# are bounded by computation, not peers.
_R7_SCOPE = ("transports/", "frontend/", "disagg/")

# Awaited terminal attribute/function names that are REQUEST-RESPONSE round
# trips against a remote peer (fire-and-forget publishes and local queue
# mutations are not flagged). Kept in sync with the Messaging/KVStore
# surface + asyncio dials.
_R7_TARGETS = {
    "request",              # Messaging.request (dispatch acks, stats)
    "queue_pop", "queue_pop_leased",       # work-queue consumption
    "dequeue", "dequeue_leased",           # PrefillQueue wrappers
    "wait_for_instances",   # discovery convergence wait
    "open_connection", "open_unix_connection",  # asyncio dials
}

# Awaiting one of these wrappers bounds whatever it wraps.
_R7_WRAPPERS = {"wait_for", "with_deadline"}


def _timeout_unbounded(call: ast.Call, tree: ast.AST) -> bool:
    """True when the call provides no effective deadline: no `timeout=`
    kwarg at all, a literal `timeout=None`, or (layer 3, flow.py) a
    timeout VARIABLE whose every reaching definition is None — asyncio
    treats timeout=None as wait-forever, so a defaulted-None local that
    never received a budget is the missing-deadline bug with extra
    steps. A variable that MAY hold a real budget on some path is given
    the benefit of the doubt (incomplete constant sets make no claim)."""
    for kw in call.keywords:
        if kw.arg != "timeout":
            continue
        v = kw.value
        if isinstance(v, ast.Constant):
            return v.value is None
        if isinstance(v, ast.Name):
            res = module_flow(tree).const_values(v)
            if res is not None:
                complete, values = res
                if complete and values == {None}:
                    return True
        return False
    return True


@rule("R7")
def r7_unbounded_transport_await(tree: ast.AST, lines: List[str],
                                 path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R7_SCOPE):
        return []
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Await) or \
                not isinstance(node.value, ast.Call):
            continue
        call = node.value
        name = _call_name(call)
        terminal = name.rsplit(".", 1)[-1]
        if terminal in _R7_WRAPPERS:
            continue
        if terminal not in _R7_TARGETS:
            continue
        if not _timeout_unbounded(call, tree):
            continue
        out.append(_finding(
            "R7", path, lines, node,
            f"`await {name}(...)` is a control-plane/transport round "
            "trip with no deadline (missing timeout=, or a timeout "
            "that resolves to None on every path) — a dead peer wedges "
            "this coroutine (and whatever stream it serves) forever",
            "pass timeout=..., or wrap in asyncio.wait_for / "
            "runtime.deadline.with_deadline bounded by the request "
            "Context's remaining budget"))
    return out


# -- R8: blocking device syncs inside hot-path REGIONS ------------------------

# Region markers scope the rule to the exact stretch of code between two
# decode-window dispatches (engine/engine.py's staging/pipeline section):
# any blocking sync there is serving latency the device cannot hide. The
# escape hatch is deliberate and auditable — `# dynalint: sync-point`
# (with a justification) on the call's line or the line above marks an
# INTENTIONAL synchronization point, e.g. the single per-window output
# fetch of the pipelined decode loop.
_R8_BEGIN_RE = re.compile(r"#\s*dynalint:\s*hot-path-begin")
_R8_END_RE = re.compile(r"#\s*dynalint:\s*hot-path-end")
_R8_SYNC_POINT_RE = re.compile(r"#\s*dynalint:\s*sync-point")
_R8_SYNC_CALLS = {"jax.device_get", "device_get"}


def _hot_path_regions(lines: List[str]) -> List[tuple]:
    regions, start = [], None
    for i, line in enumerate(lines, 1):
        if _R8_BEGIN_RE.search(line):
            start = i
        elif _R8_END_RE.search(line) and start is not None:
            regions.append((start, i))
            start = None
    if start is not None:   # unclosed region runs to EOF
        regions.append((start, len(lines)))
    return regions


def _host_side_names(tree: ast.AST) -> set:
    """Names bound from numpy calls or from a device_get — already host
    memory, so np.asarray over them is a free view, not a sync."""
    out = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) \
                or not isinstance(node.value, ast.Call):
            continue
        name = _call_name(node.value)
        if name.startswith(("np.", "numpy.")) or name in _R8_SYNC_CALLS:
            for tgt in node.targets:
                for t in ast.walk(tgt):
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


@rule("R8")
def r8_sync_in_hot_path_region(tree: ast.AST, lines: List[str],
                               path: str) -> List[Finding]:
    regions = _hot_path_regions(lines)
    if not regions:
        return []

    def in_region(ln: int) -> bool:
        return any(a <= ln <= b for a, b in regions)

    def annotated(ln: int) -> bool:
        return any(_R8_SYNC_POINT_RE.search(_line(lines, x))
                   for x in (ln, ln - 1))

    host_names = _host_side_names(tree)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not in_region(node.lineno):
            continue
        name = _call_name(node)
        sync = None
        if name in _R8_SYNC_CALLS:
            sync = f"{name}(...)"
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr == "block_until_ready":
            sync = f"{_unparse(node.func.value)}.block_until_ready()"
        elif name in ("np.asarray", "numpy.asarray") and node.args \
                and isinstance(node.args[0], ast.Name) \
                and node.args[0].id not in host_names:
            sync = f"{name}({node.args[0].id})"
        if sync is None or annotated(node.lineno):
            continue
        out.append(_finding(
            "R8", path, lines, node,
            f"blocking sync `{sync}` inside a hot-path region — the "
            "host stalls here while the device drains, then the device "
            "idles while the host catches up (the exact bubble the "
            "pipelined decode loop exists to remove)",
            "move the read to the window's single fetch, start an async "
            "copy (copy_to_host_async) instead, or annotate the line "
            "with `# dynalint: sync-point(<why this must block>)`"))
    return out


# -- R9: silently swallowed exceptions in the serving layers ------------------

# Scope: the layers where a swallowed exception hides a *peer's* failure
# from every recovery mechanism built to observe it — a lost heartbeat,
# a dropped completion notify, a failed eviction all degrade silently.
# The faults PR made this concrete: an injected FaultInjected that lands
# in an unannotated `except Exception: pass` simply vanishes, and the
# chaos run "passes" without the recovery path ever running. Engine code
# is out of scope (exceptions there surface through the step loop).
_R9_SCOPE = ("runtime/", "disagg/", "frontend/")
_R9_ANNOT_RE = re.compile(r"#\s*dynalint:\s*swallow-ok=\S+")
_R9_LOG_METHODS = {"debug", "info", "warning", "error", "exception",
                   "critical"}


def _only_passes_or_logs(body: List[ast.stmt]) -> bool:
    """True when the handler body does NO handling: just pass/continue/
    bare-return and logging calls. Anything else (fallback logic,
    cleanup, state mutation, re-raise) counts as real handling."""
    for stmt in body:
        if isinstance(stmt, (ast.Pass, ast.Continue, ast.Break)):
            continue
        if isinstance(stmt, ast.Return) and (
                stmt.value is None
                or (isinstance(stmt.value, ast.Constant)
                    and stmt.value.value is None)):
            continue
        if isinstance(stmt, ast.Expr) \
                and isinstance(stmt.value, ast.Call) \
                and isinstance(stmt.value.func, ast.Attribute) \
                and stmt.value.func.attr in _R9_LOG_METHODS:
            continue
        return False
    return True


@rule("R9")
def r9_swallowed_exception(tree: ast.AST, lines: List[str],
                           path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R9_SCOPE):
        return []

    def annotated(ln: int) -> bool:
        return any(_R9_ANNOT_RE.search(_line(lines, x))
                   for x in (ln, ln - 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler) or node.type is None:
            continue   # bare `except:` is R4's territory
        types = node.type.elts if isinstance(node.type, ast.Tuple) \
            else [node.type]
        if not any(_unparse(t) == "Exception" for t in types):
            continue   # narrow typed handlers are deliberate
        if not _only_passes_or_logs(node.body):
            continue
        if annotated(node.lineno):
            continue
        out.append(_finding(
            "R9", path, lines, node,
            "`except Exception` swallows the error (pass/log-and-"
            "continue) on a serving path — a peer failure, or an "
            "injected fault, degrades this layer silently and no "
            "recovery mechanism ever observes it",
            "handle it (retry/fallback/cleanup), re-raise, or annotate "
            "with `# dynalint: swallow-ok=<why losing this error is "
            "correct>`"))
    return out


# -- R10: unbucketed leading dims in schedule()-reachable plan builders -------

# Scope: the engine's planning layer — the scheduler and the engine step
# path — where every array built per step becomes a jitted program's
# input shape. A leading dim taken straight from `len(...)` tracks the
# live batch/slot/row count, so EVERY admission or finish changes the
# shape and XLA compiles a fresh program mid-serving (seconds of stall —
# the exact hazard the pow2/page bucket ladders exist to prevent). The
# sanctioned shapes route through next_bucket()/pow2_buckets()/
# page_bucket_ladder() first; a deliberate exception is annotated
# `# dynalint: bucketed` (with why the shape is admission-stable).
_R10_SCOPE = ("engine/scheduler", "engine/engine")
_R10_FUNC_RE = re.compile(r"^(schedule$|_schedule|_build|_stage)")
_R10_ALLOCS = {"np.zeros", "np.ones", "np.full", "np.empty",
               "numpy.zeros", "numpy.ones", "numpy.full", "numpy.empty",
               "jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty"}
_R10_ANNOT_RE = re.compile(r"#\s*dynalint:\s*bucketed")


def _contains_len_call(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call) and _call_name(n) == "len"
               for n in ast.walk(node))


# Sanctioned bucketing calls: a value routed through one is admission-
# stable by construction and stops the layer-3 derivation walk.
_R10_BUCKETS = {"next_bucket", "pow2_buckets", "page_bucket_ladder"}


def _is_bucket_call(n: ast.AST) -> bool:
    return isinstance(n, ast.Call) and \
        _call_name(n).rsplit(".", 1)[-1] in _R10_BUCKETS


def _is_len_call(n: ast.AST) -> bool:
    return isinstance(n, ast.Call) and _call_name(n) == "len"


def _lead_data_dependent(lead: ast.expr, tree: ast.AST) -> bool:
    """Does the leading shape element track the live batch? Lexically: a
    bare `len(...)` inside the element. Through layer 3 (flow.py): a
    NAME whose reaching definitions derive from `len(...)` without
    passing a sanctioned bucketing call — `n = len(batch)` one statement
    before the allocation is the documented escape this closes, while
    `n = next_bucket(len(batch), ladder)` stays quiet."""
    if _contains_len_call(lead):
        return True
    names = [n for n in ast.walk(lead) if isinstance(n, ast.Name)
             and isinstance(n.ctx, ast.Load)]
    if not names:
        return False
    mf = module_flow(tree)
    return any(mf.name_derives_from(nm, _is_len_call, _is_bucket_call)
               for nm in names)


@rule("R10")
def r10_unbucketed_plan_dims(tree: ast.AST, lines: List[str],
                             path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R10_SCOPE):
        return []

    def annotated(ln: int) -> bool:
        return any(_R10_ANNOT_RE.search(_line(lines, x))
                   for x in (ln, ln - 1))

    out: List[Finding] = []
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or not _R10_FUNC_RE.search(fn.name):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call) \
                    or _call_name(node) not in _R10_ALLOCS \
                    or not node.args:
                continue
            shape = node.args[0]
            lead = shape.elts[0] if (isinstance(shape, ast.Tuple)
                                     and shape.elts) else shape
            if not _lead_data_dependent(lead, tree):
                continue
            if annotated(node.lineno):
                continue
            out.append(_finding(
                "R10", path, lines, node,
                f"per-step array in `{fn.name}` allocated with "
                f"data-dependent leading dim `{_unparse(lead)}` — the "
                "shape tracks the live batch, so every admission mints "
                "a NEW compiled XLA program (seconds-long serving "
                "stall)",
                "round the dim through next_bucket()/pow2_buckets() "
                "like the plan builders do, or annotate with "
                "`# dynalint: bucketed` and say why the shape is "
                "admission-stable"))
    return out


# -- R11: raw KV-cache leaf access outside the quant codec helpers ------------

# Scope: model forward code, the ops layer, and the engine's jitted step
# path — everywhere a cache leaf can reach arithmetic. With
# ModelConfig.kv_quant the "k"/"v" leaves hold int8 bytes whose VALUES
# only exist after the ops/kv_quant.py codec applies the scale rows; a
# raw `cache["k"]` index (or `.astype` to a float dtype) that bypasses
# the codec reads garbage that is bitwise-plausible and numerically
# wrong — the worst kind of quantization bug. Codec-aware sites (reads
# that hand leaves to a dequantizing consumer, whole-page moves that
# keep the representation) carry `# dynalint: kv-codec` on the access
# or the preceding two lines; ops/kv_quant.py itself IS the codec.
_R11_SCOPE = ("models/", "ops/", "engine/engine")
_R11_EXEMPT = ("ops/kv_quant",)
_R11_KEYS = {"k", "v", "k_scale", "v_scale"}
_R11_ANNOT_RE = re.compile(r"#\s*dynalint:\s*kv-codec")
_R11_FLOAT_RE = re.compile(r"float|bfloat|bf16|f16|f32|fp16")
_R11_HINT = (
    "route the read/write through ops/kv_quant.py (quantize_"
    "rows / dequantize_rows, ops/attention.gather_values) or the codec-"
    "aware attention/write helpers, or annotate with "
    "`# dynalint: kv-codec` and say how the site preserves or "
    "decodes the representation")


@rule("R11")
def r11_raw_kv_cache_access(tree: ast.AST, lines: List[str],
                            path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R11_SCOPE) \
            or any(part in norm for part in _R11_EXEMPT):
        return []

    def annotated(ln: int) -> bool:
        return any(_R11_ANNOT_RE.search(_line(lines, x))
                   for x in (ln, ln - 1, ln - 2))

    def is_cache_base(expr: ast.AST) -> bool:
        # a name or attribute whose last component is `cache`
        # (cache, self.cache, eng.cache)
        return (isinstance(expr, ast.Name) and expr.id == "cache") or \
            (isinstance(expr, ast.Attribute) and expr.attr == "cache")

    mf = None

    def aliases(name_node: ast.Name) -> list:
        nonlocal mf
        if mf is None:
            mf = module_flow(tree)
        return mf.alias_exprs(name_node)

    def aliases_cache(base: ast.AST) -> bool:
        """base aliases the cache dict through layer-3 name copies
        (`kv = cache` / `kv = self.cache`, the documented escape)."""
        return isinstance(base, ast.Name) and \
            any(is_cache_base(a) for a in aliases(base))

    def value_leaf_alias(name_node: ast.Name) -> Optional[ast.expr]:
        """The `<cache-ish>["k"|"v"]` expression `name_node` aliases
        (directly or through a cache-dict alias), or None."""
        for a in aliases(name_node):
            if isinstance(a, ast.Subscript) and \
                    isinstance(a.slice, ast.Constant) and \
                    a.slice.value in ("k", "v") and \
                    (is_cache_base(a.value) or aliases_cache(a.value)):
                return a
        return None

    out: List[Finding] = []
    flagged: set = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Subscript):
            continue
        sl = node.slice
        if not (isinstance(sl, ast.Constant) and sl.value in _R11_KEYS):
            continue
        base = node.value
        direct = is_cache_base(base)
        if not direct and not aliases_cache(base):
            continue
        if annotated(node.lineno):
            continue
        via = "" if direct else (
            f" (`{_unparse(base)}` aliases the cache dict — layer-3 "
            "alias tracking)")
        flagged.add(node.lineno)
        out.append(_finding(
            "R11", path, lines, node,
            f"raw KV-cache leaf access `{_unparse(node)}`{via} outside "
            "the kv_quant codec helpers — with kv_quant='int8' this "
            "leaf holds quantized bytes (+scale rows elsewhere); "
            "indexing or casting it directly treats int8 bytes as "
            "values",
            _R11_HINT))

    # layer 3: downstream arithmetic on an ALIAS of a value leaf —
    #   k = cache["k"]            (maybe annotated as a whole-page move)
    #   ...; x = k.astype(jnp.float32); y = k * scale
    # the alias carries quantized bytes out of the annotated site and
    # into float math, which is exactly the bytes-as-values bug the
    # lexical rule could not follow.
    for node in ast.walk(tree):
        cands: list = []
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "astype" and \
                isinstance(node.func.value, ast.Name) and node.args and \
                _R11_FLOAT_RE.search(_unparse(node.args[0])):
            cands = [(node.func.value,
                      f".astype({_unparse(node.args[0])})")]
        elif isinstance(node, ast.BinOp):
            cands = [(s, "arithmetic") for s in (node.left, node.right)
                     if isinstance(s, ast.Name)]
        for nm, how in cands:
            if node.lineno in flagged or annotated(node.lineno):
                continue
            leaf = value_leaf_alias(nm)
            if leaf is None:
                continue
            flagged.add(node.lineno)
            out.append(_finding(
                "R11", path, lines, node,
                f"`{nm.id}` aliases KV-cache value leaf "
                f"`{_unparse(leaf)}` and feeds {how} (layer-3 alias "
                "tracking) — with kv_quant='int8' the alias carries "
                "quantized bytes, and float math on them treats bytes "
                "as values",
                _R11_HINT))
    return out


# -- R12: control-plane retry loops without backoff+jitter --------------------

# Scope: the layers whose retry loops hit the discovery store / event
# plane — the watch pumps, heartbeat/keepalive loops, lease renewal and
# scrape loops. The churn-storm failure mode is collective: one loop
# retrying hot is a nuisance, a THOUSAND of them synchronized by the
# same outage is a thundering herd that keeps the store down. A loop is
# a *retry loop* when (a) it is a `while` loop that (b) contains an
# exception handler that does not re-raise (the loop survives failures
# and goes around again) and (c) touches a control-plane reconnect /
# renewal target. The sanctioned fix is runtime/backoff.py (any name
# containing "backoff" in the loop body counts); a deliberately
# fixed-cadence loop (TTL-paced heartbeat, fixed-interval scrape)
# carries `# dynalint: backoff-ok=<reason>` on the `while` line or the
# line above.
_R12_SCOPE = ("runtime/", "frontend/", "kv_router/")
_R12_TARGETS = {
    "watch_prefix", "subscribe", "grant_lease", "keep_alive",
    "scrape_once", "scrape_stats", "_rpc", "lease_keepalive", "register",
}
_R12_ANNOT_RE = re.compile(r"#\s*dynalint:\s*backoff-ok=\S+")
_R12_BACKOFF_RE = re.compile(r"backoff", re.I)


def _loop_own_nodes(loop: ast.While):
    """Nodes in the loop's own body, not descending into nested
    function/class definitions (their loops are their own problem)."""
    stack = list(loop.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


@rule("R12")
def r12_retry_loop_without_backoff(tree: ast.AST, lines: List[str],
                                   path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R12_SCOPE):
        return []

    def annotated(ln: int) -> bool:
        return any(_R12_ANNOT_RE.search(_line(lines, x))
                   for x in (ln, ln - 1))

    out: List[Finding] = []
    for loop in ast.walk(tree):
        if not isinstance(loop, ast.While):
            continue
        survives = False
        target = None
        has_backoff = False
        for node in _loop_own_nodes(loop):
            if isinstance(node, ast.ExceptHandler) \
                    and not _handler_reraises(node):
                survives = True
            if isinstance(node, ast.Call):
                terminal = _call_name(node).rsplit(".", 1)[-1]
                if terminal in _R12_TARGETS:
                    target = target or terminal
            if isinstance(node, ast.Name) \
                    and _R12_BACKOFF_RE.search(node.id):
                has_backoff = True
            if isinstance(node, ast.Attribute) \
                    and _R12_BACKOFF_RE.search(node.attr):
                has_backoff = True
        if not (survives and target) or has_backoff:
            continue
        if annotated(loop.lineno):
            continue
        out.append(_finding(
            "R12", path, lines, loop,
            f"control-plane retry loop around `{target}` survives "
            "failures with no backoff+jitter — under a storm, every "
            "worker running this loop retries in the SAME synchronized "
            "wave, hammering the store that is trying to recover",
            "drive the retry delay through runtime/backoff.py (bounded "
            "exponential + seeded jitter + flap hysteresis), or "
            "annotate the loop with `# dynalint: backoff-ok=<why a "
            "fixed cadence is correct here>`"))
    return out


# -- R13: span lifecycle + hot-path span deferral -----------------------------

# Two halves of one tracing contract (runtime/tracing.py):
# (a) a manually-begun span (`begin_span`) MUST be ended on every path —
#     either the call is a `with` context expression, or an enclosing
#     try's finally contains an `end_span`/`.finish()` — otherwise an
#     early return/exception leaks the span and the trace tree shows a
#     request that "never finished" (the exact artifact trace_explain
#     exists to rule out);
# (b) inside `# dynalint: hot-path-begin/end` regions, span-RECORDING
#     calls (TRACER.span/begin_span/event/record_span/scope_span) are
#     forbidden — they allocate and walk attrs between two device
#     dispatches; the deferred recorder (`defer_phase`, what PhaseTimer
#     routes through) is the only allowed form there. A bare
#     `jax.profiler.TraceAnnotation` / `StepTraceAnnotation` in a region
#     is flagged as well: `PhaseTimer.phase` opens the annotation itself,
#     and a second one would enclose or split the phases a trace reducer
#     labels idle gaps by.
# Escape hatch: `# dynalint: span-ok=<reason>` on the line or the line
# above (e.g. the frontend root span that ends in an idempotent
# finish() callback every exit funnels through).

_R13_BEGIN = "begin_span"
_R13_END = {"end_span", "finish"}
_R13_RECORDING = {"span", "begin_span", "start_span", "event",
                  "record_span", "scope_span"}
_R13_PROFILER = {"TraceAnnotation", "StepTraceAnnotation"}
_R13_ANNOT_RE = re.compile(r"#\s*dynalint:\s*span-ok=\S+")


def _calls_named(node: ast.AST, names) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            term = _call_name(n).rsplit(".", 1)[-1]
            if term in names:
                return True
    return False


@rule("R13")
def r13_span_lifecycle(tree: ast.AST, lines: List[str],
                       path: str) -> List[Finding]:
    def annotated(ln: int) -> bool:
        return any(_R13_ANNOT_RE.search(_line(lines, x))
                   for x in (ln, ln - 1))

    out: List[Finding] = []

    # (a) begin_span without a guaranteed end ---------------------------------
    safe: set = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                for n in ast.walk(item.context_expr):
                    if isinstance(n, ast.Call) and \
                            _call_name(n).rsplit(".", 1)[-1] == _R13_BEGIN:
                        safe.add(id(n))
        elif isinstance(node, ast.Try) and node.finalbody:
            ends = any(_calls_named(fin, _R13_END)
                       for fin in node.finalbody)
            if not ends:
                continue
            for stmt in node.body:
                for n in ast.walk(stmt):
                    if isinstance(n, ast.Call) and \
                            _call_name(n).rsplit(".", 1)[-1] == _R13_BEGIN:
                        safe.add(id(n))
    # a begin_span bound to a name is safe when layer 3 (flow.py)
    # PROVES every CFG path from the binding reaches an end_span /
    # .finish() — the assign-then-try/finally idiom, branch-complete
    # endings — and when the call's result is immediately returned
    # (ownership transfers to the caller). This replaces the old
    # function-local heuristic ("some try/finally in the function ends
    # some span"), which blessed every begin_span in a function that
    # correctly ended ONE of them.
    mf = None

    def _ends(cfg_node: ast.AST) -> bool:
        for root in header_exprs(cfg_node):
            for n in ast.walk(root):
                if isinstance(n, ast.Call) and \
                        _call_name(n).rsplit(".", 1)[-1] in _R13_END:
                    return True
        return False

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or _call_name(node).rsplit(".", 1)[-1] != _R13_BEGIN \
                or id(node) in safe:
            continue
        if mf is None:
            mf = module_flow(tree)
        fl = mf.flow_for(node)
        if fl is None:
            continue
        stmt = fl.stmt_of(node)
        if isinstance(stmt, ast.Return):
            safe.add(id(node))  # span factory: the caller owns the end
            continue
        if fl.always_reaches_after(node, _ends):
            safe.add(id(node))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or _call_name(node).rsplit(".", 1)[-1] != _R13_BEGIN:
            continue
        if id(node) in safe or annotated(node.lineno):
            continue
        out.append(_finding(
            "R13", path, lines, node,
            "`begin_span(...)` with no guaranteed end — an early "
            "return or exception leaks the span and the trace shows a "
            "request that never finished",
            "use `with TRACER.span(...)`, or end the span in a "
            "try/finally (`TRACER.end_span(span)`), or annotate with "
            "`# dynalint: span-ok=<why every path still ends it>`"))

    # (b) recording calls inside hot-path regions -----------------------------
    regions = _hot_path_regions(lines)
    if regions:
        def in_region(ln: int) -> bool:
            return any(a <= ln <= b for a, b in regions)

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) \
                    or not in_region(node.lineno):
                continue
            name = _call_name(node)
            term = name.rsplit(".", 1)[-1]
            if annotated(node.lineno):
                continue
            if term in _R13_PROFILER:
                out.append(_finding(
                    "R13", path, lines, node,
                    f"bare profiler annotation `{name}(...)` inside a "
                    "hot-path region — the phases of a step are flat, and "
                    "an annotation of its own encloses or splits them",
                    "time the stretch with `PhaseTimer.phase(name)`, "
                    "which opens the annotation, or annotate with "
                    "`# dynalint: span-ok=<reason>`"))
                continue
            if term not in _R13_RECORDING or "tracer" not in name.lower():
                continue
            out.append(_finding(
                "R13", path, lines, node,
                f"span-recording call `{name}(...)` inside a hot-path "
                "region — span objects and attr dicts between two "
                "decode-window dispatches are host time the device "
                "cannot hide",
                "record through the deferred recorder instead "
                "(`TRACER.defer_phase(scope, name, dt)` — what "
                "PhaseTimer.phase routes through), or annotate with "
                "`# dynalint: span-ok=<reason>`"))
    return out


# -- R14: unbounded raw stream IO on the wire ---------------------------------

# Scope: the layers that own raw sockets — the disagg data plane and the
# transport implementations. R7 already bounds the named higher-level
# round trips (request, queue_pop, open_connection, ...); R14 covers the
# primitive stream ops UNDER them, which is where a half-open peer or a
# receiver that stops reading actually wedges a coroutine: a frame read
# against a dead decode worker, a `drain()` against a peer whose recv
# window is full. Every such await must be bounded — a `timeout=` kwarg
# (read_frame grew one), an `asyncio.wait_for` in the same await
# expression — or carry `# dynalint: unbounded-io-ok=<reason>` within
# three lines above (the sanctioned cases: server-side pumps reading
# from legitimately-idle client connections, where death surfaces as
# EOF, and bodies that run entirely under one enclosing wait_for).
_R14_SCOPE = ("disagg/", "runtime/transports/")
_R14_TARGETS = {"read_frame", "readexactly", "readuntil", "readline",
                "drain"}
_R14_ANNOT_RE = re.compile(r"#\s*dynalint:\s*unbounded-io-ok=\S+")


@rule("R14")
def r14_unbounded_stream_io(tree: ast.AST, lines: List[str],
                            path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R14_SCOPE):
        return []

    def annotated(ln: int) -> bool:
        return any(_R14_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Await) or \
                not isinstance(node.value, ast.Call):
            continue
        call = node.value
        name = _call_name(call)
        terminal = name.rsplit(".", 1)[-1]
        if terminal not in _R14_TARGETS:
            # a wait_for(...) wrapper makes the terminal "wait_for";
            # the raw op inside it is bounded by construction
            continue
        if not _timeout_unbounded(call, tree):
            continue
        if annotated(node.lineno):
            continue
        out.append(_finding(
            "R14", path, lines, node,
            f"`await {name}(...)` is a raw stream read/write with no "
            "deadline (missing timeout=, or a timeout that resolves to "
            "None on every path) — a half-open peer (or one that stops "
            "reading) "
            "wedges this coroutine, and with it the transfer/queue slot "
            "it serves, until process restart",
            "bound it: pass timeout= (read_frame supports it), wrap in "
            "asyncio.wait_for, or annotate with "
            "`# dynalint: unbounded-io-ok=<why an unbounded wait is "
            "correct here>` (e.g. an idle server-side pump whose peer "
            "death surfaces as EOF)"))
    return out


# -- R15: metric registrations need help text + a docs-catalog entry ----------

# Scope: the dynamo_tpu package (not tools/tests — ad-hoc analysis
# histograms there aren't operator-facing). A `registry.counter/gauge/
# histogram(name, help, ...)` registration is the operator contract for
# a metric family: HELP renders on every /metrics scrape, and
# docs/OBSERVABILITY.md's metric catalog is what the completeness test
# (tests/test_metrics_catalog.py) checks rendered output against — an
# undocumented family is invisible to the runbooks, a doc-only family
# is a silent plumbing regression waiting to happen. The rule resolves
# f-string names by their literal fragments (a dict-comprehension over
# `f"llm_cp_{name}"` passes if ANY catalog family matches the
# fragments in order); a name with no literal fragments is statically
# unresolvable and skipped. Escape: `# dynalint: metric-doc-ok=<reason>`
# within two lines above.
_R15_METHODS = {"counter", "gauge", "histogram"}
_R15_ANNOT_RE = re.compile(r"#\s*dynalint:\s*metric-doc-ok=\S+")
_R15_FAMILY_RE = re.compile(r"`([a-z][a-z0-9_]*)`")
_R15_CATALOG: Optional[frozenset] = None


def _metric_catalog() -> Optional[frozenset]:
    """Backticked llm_* family names in docs/OBSERVABILITY.md's metric
    catalog section; None when the doc is unreadable (rule degrades to
    help-text-only rather than flagging everything)."""
    global _R15_CATALOG
    if _R15_CATALOG is not None:
        return _R15_CATALOG
    import os
    doc = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))),
        "docs", "OBSERVABILITY.md")
    try:
        with open(doc) as f:
            text = f.read()
    except OSError:
        return None
    # the catalog section only: from its header to the next "## "
    m = re.search(r"^##[^\n]*metric catalog.*?$", text,
                  re.I | re.M)
    if m is None:
        return None
    tail = text[m.end():]
    nxt = re.search(r"^## ", tail, re.M)
    section = tail[:nxt.start()] if nxt else tail
    _R15_CATALOG = frozenset(
        name for name in _R15_FAMILY_RE.findall(section)
        if name.startswith("llm_"))
    return _R15_CATALOG


def _r15_name_fragments(node: ast.expr) -> Optional[List[str]]:
    """Literal fragments of a metric-name expression, in order; None
    when the expression carries no resolvable literal text."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value] if node.value else None
    if isinstance(node, ast.JoinedStr):
        frags = [v.value for v in node.values
                 if isinstance(v, ast.Constant)
                 and isinstance(v.value, str) and v.value]
        return frags or None
    return None


@rule("R15")
def r15_metric_registration_contract(tree: ast.AST, lines: List[str],
                                     path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if "dynamo_tpu/" not in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R15_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 2, ln + 1))

    catalog = _metric_catalog()
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or \
                not isinstance(node.func, ast.Attribute) or \
                node.func.attr not in _R15_METHODS:
            continue
        if not node.args:
            continue
        frags = _r15_name_fragments(node.args[0])
        if frags is None and not isinstance(
                node.args[0], (ast.Constant, ast.JoinedStr)):
            continue    # non-literal name: not a registration we can see
        if annotated(node.lineno):
            continue
        label = "".join(frags) if frags else "<dynamic>"
        # (a) non-empty help text
        help_arg = node.args[1] if len(node.args) > 1 else next(
            (kw.value for kw in node.keywords if kw.arg == "help_"), None)
        helpless = help_arg is None or (
            isinstance(help_arg, ast.Constant)
            and isinstance(help_arg.value, str)
            and not help_arg.value.strip())
        if helpless:
            out.append(_finding(
                "R15", path, lines, node,
                f"metric registration {label!r} has no help text — "
                "HELP renders empty on every /metrics scrape and the "
                "operator reading a storm has nothing to go on",
                "pass a non-empty help string (second argument)"))
        # (b) family documented in the docs/OBSERVABILITY.md catalog
        if catalog is None or frags is None:
            continue
        pattern = ".*".join(re.escape(f) for f in frags)
        if not (frags[0].startswith("llm_") or pattern.startswith("llm")):
            pattern = ".*" + pattern
        rx = re.compile(pattern + ".*")
        if not any(rx.fullmatch(fam) for fam in catalog):
            out.append(_finding(
                "R15", path, lines, node,
                f"metric family {label!r} is not in the "
                "docs/OBSERVABILITY.md metric catalog — undocumented "
                "families are invisible to the runbooks and exempt from "
                "the catalog completeness test (silent plumbing "
                "regressions)",
                "add the family to the catalog table in "
                "docs/OBSERVABILITY.md (with its surface), or annotate "
                "with `# dynalint: metric-doc-ok=<reason>`"))
    return out


# -- R16: transfer-cost estimates must handle the no-data branch --------------

# Scope: the dynamo_tpu package and tools/ (the serving path and the
# diagnosis tooling both consume TransferCostModel). The model's scalar
# queries (`estimate_s`, `bandwidth_bytes_per_s`) and its structured
# `estimate()` (matched only on cost/model receivers, to avoid generic
# `estimate` methods elsewhere) silently answer from a PRIOR when the
# link has no measured EWMA — the fleet-median fallback — and from a
# FROZEN value under the router's stale-snapshot degraded mode. A
# consumer that can't tell prior from measurement over-commits to
# unmeasured links, so the rule demands the enclosing function visibly
# engage the fallback vocabulary (cold/measured/frozen/degraded/
# default/median/fallback — a `.cold` branch, a `measured()` check, a
# freeze flag, a documented default) or carry
# `# dynalint: cost-fallback-ok=<reason>` within three lines above.
_R16_SCOPE = ("dynamo_tpu/", "tools/")
_R16_SCALARS = {"estimate_s", "bandwidth_bytes_per_s"}
_R16_ANNOT_RE = re.compile(r"#\s*dynalint:\s*cost-fallback-ok=\S+")
_R16_HANDLED_RE = re.compile(
    r"cold|measured|frozen|degraded|default|median|fallback", re.I)


@rule("R16")
def r16_cost_fallback_contract(tree: ast.AST, lines: List[str],
                               path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R16_SCOPE) \
            or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R16_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            # module-level consumer: scan a window around the call
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R16_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        terminal = name.rsplit(".", 1)[-1]
        if terminal in _R16_SCALARS:
            pass
        elif terminal == "estimate" and (
                "model" in name.lower() or "cost" in name.lower()):
            pass
        else:
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R16", path, lines, node,
            f"`{name}(...)` consumes a transfer-cost estimate without "
            "handling the no-data branch — a cold link answers from the "
            "fleet-median PRIOR and a degraded router answers from a "
            "FROZEN value; treating either as a measurement over-commits "
            "traffic onto links nobody has measured",
            "branch on the estimate's `cold` flag (or `.measured()`/"
            "the selector's freeze state), document the default, or "
            "annotate with `# dynalint: cost-fallback-ok=<why the "
            "fallback is safe here>`"))
    return out


# -- R17: fleet actuations in loops/controller ticks must be paced ------------

# Scope: the dynamo_tpu package and tools/ (controllers and storm
# drivers both actuate). The actuators this repo ships — graceful drain
# (`mark_draining`/`.drain()` on a worker-shaped receiver) and role
# re-registration (`set_role`/`re_role`/`re_register`) — are safe as
# one-shot operator actions; the failure mode is the LOOP: a controller
# tick or retry loop that actuates on every pass turns one bad sensor
# reading into a fleet-wide drain. The rule demands the enclosing
# function visibly engage pacing (cooldown/hysteresis/backoff/jitter —
# the runtime/autoscaler.py Cooldown+Hysteresis objects, a Backoff, a
# seeded jittered restart) or carry `# dynalint: actuation-ok=<reason>`
# within three lines above. Lexical like R16: the pacing argument
# should be written down where the actuation happens.
_R17_SCOPE = ("dynamo_tpu/", "tools/")
_R17_ALWAYS = {"mark_draining", "set_role", "re_role", "re_register"}
_R17_DRAIN_RECV_RE = re.compile(
    r"worker|endpoint|served|instance|engine_proc", re.I)
_R17_ANNOT_RE = re.compile(r"#\s*dynalint:\s*actuation-ok=\S+")
_R17_PACED_RE = re.compile(r"cooldown|hysteresis|backoff|jitter", re.I)
_R17_TICK_FN_RE = re.compile(r"tick|actuate|controller|rebalance", re.I)


def _r17_is_actuation(node: ast.Call) -> bool:
    name = _call_name(node)
    terminal = name.rsplit(".", 1)[-1]
    if terminal in _R17_ALWAYS:
        return True
    if terminal == "drain":
        recv = name.rsplit(".", 1)[0] if "." in name else ""
        return bool(_R17_DRAIN_RECV_RE.search(recv))
    return False


@rule("R17")
def r17_actuation_pacing_contract(tree: ast.AST, lines: List[str],
                                  path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R17_SCOPE) or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R17_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing(ln: int):
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        return inner

    def paced(ln: int) -> bool:
        fn = enclosing(ln)
        if fn is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = fn.lineno, getattr(fn, "end_lineno", fn.lineno)
        return any(_R17_PACED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    # actuations inside a loop, plus every actuation in a function
    # whose name says it IS the repeated context (a controller tick)
    suspects: Dict[int, ast.Call] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.While, ast.For, ast.AsyncFor)):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and _r17_is_actuation(sub):
                    suspects[sub.lineno] = sub
    for fn in funcs:
        if not _R17_TICK_FN_RE.search(fn.name):
            continue
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call) and _r17_is_actuation(sub):
                suspects[sub.lineno] = sub

    out: List[Finding] = []
    for ln in sorted(suspects):
        node = suspects[ln]
        if annotated(ln) or paced(ln):
            continue
        out.append(_finding(
            "R17", path, lines, node,
            f"`{_call_name(node)}(...)` actuates a drain/re-role inside "
            "a loop or controller tick without visible pacing — an "
            "unpaced actuation loop lets one wedged sensor mass-drain "
            "the fleet (every tick moves more workers)",
            "pace the loop with a cooldown/hysteresis object "
            "(runtime/autoscaler.py Cooldown/Hysteresis), a Backoff, or "
            "seeded jitter, or annotate with "
            "`# dynalint: actuation-ok=<why unpaced actuation is safe "
            "here>`"))
    return out


# -- R18: shared-pool data paths must reference checksum verification ---------

# Scope: the dynamo_tpu package and tools/ (the serving path and the
# diagnosis tooling both touch pool pages). The shared pool
# (engine/kv_pool.py SharedKvPool) moves KV pages ACROSS worker
# boundaries keyed only by content hash — there is no allocator epoch or
# scheduler.remote guard between a pool entry and a device cache, the
# traveling capture checksum is the whole integrity story. The rule is
# lexical like R16: the enclosing function must write down where that
# verification happens (checksum/verify/integrity/quarantine vocabulary
# — a docstring pointing at the claim-time verify counts, and should) or
# the call carries `# dynalint: pool-verify-ok=<reason>` within three
# lines above. Matched calls: `publish` / `fetch` / `note_source` on a
# receiver whose dotted name mentions "pool" (SharedKvPool handles;
# HostKvPool exposes none of these, so the private tiers stay quiet),
# any `*pool*claim*` terminal, and `prefetch_pool_pages` anywhere.
_R18_SCOPE = ("dynamo_tpu/", "tools/")
_R18_POOL_TERMINALS = {"publish", "fetch", "note_source"}
_R18_ANNOT_RE = re.compile(r"#\s*dynalint:\s*pool-verify-ok=\S+")
_R18_HANDLED_RE = re.compile(r"checksum|verif|integrity|quarantin", re.I)


def _r18_is_pool_call(node: ast.Call) -> bool:
    name = _call_name(node)
    terminal = name.rsplit(".", 1)[-1]
    if terminal == "prefetch_pool_pages":
        return True
    low = terminal.lower()
    if "pool" in low and "claim" in low:
        return True
    if terminal not in _R18_POOL_TERMINALS:
        return False
    recv = name.rsplit(".", 1)[0] if "." in name else ""
    return "pool" in recv.lower()


@rule("R18")
def r18_pool_verification_contract(tree: ast.AST, lines: List[str],
                                   path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R18_SCOPE) \
            or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R18_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R18_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not _r18_is_pool_call(node):
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R18", path, lines, node,
            f"`{_call_name(node)}(...)` moves shared-pool KV pages "
            "without referencing checksum verification — pool pages "
            "cross worker boundaries with the traveling capture checksum "
            "as their ONLY integrity guard, and a data path that doesn't "
            "state where verify-on-fetch happens is where a refactor "
            "silently drops it",
            "state (docstring/comment) where the capture checksum is "
            "verified for this path — e.g. 'checksum-verified at claim "
            "(SharedKvPool.fetch), quarantine on mismatch' — or "
            "annotate with `# dynalint: pool-verify-ok=<why no "
            "verification is needed here>`"))
    return out


# -- R19: preemption/victim-selection must reference the starvation bound -----

# Scope: the dynamo_tpu package and tools/ (the engine scheduler, the
# disagg queue consumers, and the QoS storm driver all preempt or
# class-order work). Multi-tenant QoS (runtime/qos.py) made preemption
# and class-ordered dequeue POLICY — and every such decision point is
# one refactor away from unbounded starvation (the high class wins
# every contest, the batch tenant never completes). The mitigation is
# one shared bound: `QosPolicy.aging_limit` (queue bypass pinning,
# StridePicker aging promotion, class-band victim requeue), plus the
# per-class preemption budget. The rule is lexical like R16/R18: the
# enclosing function must write the bound down (aging|starv
# vocabulary) or the call carries `# dynalint: starvation-ok=<reason>`
# within three lines above.
_R19_SCOPE = ("dynamo_tpu/", "tools/")
_R19_TERMINALS = {"_preempt_one", "_preempt_for", "preempt_for",
                  "select_victim", "dequeue_leased"}
_R19_ANNOT_RE = re.compile(r"#\s*dynalint:\s*starvation-ok=\S+")
_R19_HANDLED_RE = re.compile(r"aging|starv", re.I)


@rule("R19")
def r19_starvation_bound_contract(tree: ast.AST, lines: List[str],
                                  path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R19_SCOPE) \
            or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R19_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R19_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        terminal = _call_name(node).rsplit(".", 1)[-1]
        if terminal not in _R19_TERMINALS:
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R19", path, lines, node,
            f"`{_call_name(node)}(...)` preempts or class-orders work "
            "without referencing the aging/no-starvation bound — a "
            "priority decision point that can't point at its bound "
            "(QosPolicy.aging_limit, the class-band requeue, the "
            "preemption budget) is where a refactor silently lets the "
            "high class win every contest and the batch tenant never "
            "complete",
            "state (docstring/comment) where the starvation bound is "
            "enforced for this path — e.g. 'victim starvation bounded "
            "by the class-band requeue + queue aging limit' — or "
            "annotate with `# dynalint: starvation-ok=<why unbounded "
            "priority is safe here>`"))
    return out


# -- R20: committed-frontier consumers must reference the min aggregation -----

# Scope: the dynamo_tpu package and tools/ (the transfer servers, the
# disagg workers, the scheduler's overlap gates, and the bench/chaos
# drivers all consume committed frontiers). Sharded parallel transfer
# (disagg/remote_transfer.py) made the committed frontier PER-STREAM:
# each (shard, host) stream commits independently, and the request-wide
# frontier — the number salvage charges, the early-decode gate opens
# on, and resume reasons about — is the MIN over streams. Every
# consumer is one refactor away from trusting a single stream's
# frontier (salvaging pages whose sibling slices never landed = decoded
# garbage). The rule is lexical like R16/R18/R19: the enclosing
# function must write the aggregation down (min/aggregat/straggler
# vocabulary) or the call carries `# dynalint: frontier-ok=<reason>`
# within three lines above.
_R20_SCOPE = ("dynamo_tpu/", "tools/")
_R20_TERMINALS = {"stream_frontier", "committed_frontier",
                  "salvage_remote", "preactivate_remote",
                  "poll_overlap_gates"}
_R20_ANNOT_RE = re.compile(r"#\s*dynalint:\s*frontier-ok=\S+")
_R20_HANDLED_RE = re.compile(r"\bmin\b|min-frontier|min over|aggregat|"
                             r"straggler", re.I)


@rule("R20")
def r20_min_frontier_contract(tree: ast.AST, lines: List[str],
                              path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R20_SCOPE) \
            or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R20_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R20_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        terminal = _call_name(node).rsplit(".", 1)[-1]
        if terminal not in _R20_TERMINALS:
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R20", path, lines, node,
            f"`{_call_name(node)}(...)` consumes a committed transfer "
            "frontier without referencing the min-over-streams "
            "aggregation — sharded parallel transfer commits each "
            "(shard, host) stream independently, and a consumer that "
            "can't point at the min is where a refactor silently "
            "trusts one stream's frontier and salvages pages whose "
            "sibling slices never landed",
            "state (docstring/comment) where the min-frontier "
            "aggregation happens for this path — e.g. 'frontier = min "
            "over per-stream frontiers (ShardedKvTransferGroup)' — or "
            "annotate with `# dynalint: frontier-ok=<why a single "
            "stream's frontier is safe here>`"))
    return out


# -- R22: placement results are only valid under their ownership epoch --------

# Scope: the dynamo_tpu package and tools/ (the pool service, the
# router's pool scoring, the schedulers, and any future bench/ops
# driver all resolve consistent-hash placement). The cross-host pool
# (engine/pool_service.py) made ownership DYNAMIC: the HashRing bumps
# its epoch on every membership change, publishes carry that epoch and
# serving hosts fence mismatches, and fetch walks re-resolve owners
# per page. Every consumer of `owners_for(...)` / `ring.lookup(...)` /
# the pool-host resolution calls is one refactor away from caching an
# owner list across a join/leave and writing to hosts that no longer
# own the key — the zombie-sender bug class, one layer down. Lexical
# like R16/R18-R20: the enclosing function must write the
# epoch/membership discipline down, or the call carries
# `# dynalint: ring-ok=<reason>` within three lines above.
# runtime/placement.py is the placement layer itself — exempt (the
# R11 ops/kv_quant.py precedent).
_R22_SCOPE = ("dynamo_tpu/", "tools/")
_R22_EXEMPT = ("runtime/placement.py",)
_R22_TERMINALS = {"owners_for", "owners_with_epoch", "live_hosts",
                  "owner_hosts"}
_R22_ANNOT_RE = re.compile(r"#\s*dynalint:\s*ring-ok=\S+")
# receiver names alone (`ring.`, `membership.`) must NOT satisfy the
# rule — every consumer spells those — so the vocabulary is the epoch
# DISCIPLINE itself: when the answer goes stale and who fences it
_R22_HANDLED_RE = re.compile(r"epoch|\bstale\b|fenc|re-?resolv|"
                             r"\bwatch\b|replica|rebalanc|"
                             r"membership +chang|join/leave", re.I)


@rule("R22")
def r22_placement_epoch_contract(tree: ast.AST, lines: List[str],
                                 path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R22_SCOPE) \
            or "tests/" in norm \
            or any(part in norm for part in _R22_EXEMPT):
        return []

    def annotated(ln: int) -> bool:
        return any(_R22_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R22_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        terminal = name.rsplit(".", 1)[-1]
        # bare `lookup` is too generic; only the ring's lookup counts
        if terminal not in _R22_TERMINALS \
                and not name.endswith("ring.lookup"):
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R22", path, lines, node,
            f"`{name}(...)` consumes a consistent-hash placement "
            "result without referencing the ownership-epoch / "
            "membership discipline — the ring bumps its epoch on "
            "every join/leave and a cached owner list is stale the "
            "moment membership changes; a consumer that can't point "
            "at the epoch is where a refactor writes to (or fetches "
            "from) hosts that no longer own the key",
            "state (docstring/comment) how this path tracks membership "
            "— e.g. 'owners re-resolved per page; writes carry "
            "ring_epoch and hosts fence mismatches' — or annotate "
            "with `# dynalint: ring-ok=<why a stale owner list is "
            "safe here>`"))
    return out


# -- R23: one decode kernel — direct pallas_call forks must be declared -------

# Scope: the dynamo_tpu package and tools/ (bench/profile drivers are
# exactly where a "quick local kernel" fork gets pasted). PR 18
# collapsed _decode_kernel / _decode_kernel_packed /
# _decode_kernel_prefix into ONE ragged kernel dispatched from
# ops/paged_attention.py; the frozen pre-PR-18 copies survive only in
# ops/paged_attention_oracle.py as parity oracles. A decode-attention
# `pl.pallas_call` constructed anywhere else is a kernel fork: it
# starts life without the stale-tail zeroing (R2) and int8
# scale-folding defenses and drifts from the dispatcher on the next
# geometry change. Lexical like R22: the call must carry
# `# dynalint: kernel-ok=<reason>` within three lines above.
# ops/paged_attention.py is the dispatcher itself — exempt (the R11
# ops/kv_quant.py precedent). The oracle module is in scope on
# purpose: its two frozen call sites carry the annotation, so a THIRD
# copy pasted there still flags.
_R23_SCOPE = ("dynamo_tpu/", "tools/")
_R23_EXEMPT = ("ops/paged_attention.py",)
_R23_ANNOT_RE = re.compile(r"#\s*dynalint:\s*kernel-ok=\S+")


def _r23_mentions_decode(node: ast.AST) -> bool:
    """True when any identifier under `node` names a decode kernel.

    Catches the kernel passed bare (`_decode_kernel_packed`), through
    `functools.partial(_ragged_decode_kernel, ...)`, or as an
    attribute (`mod._decode_kernel`).
    """
    for sub in ast.walk(node):
        ident = sub.id if isinstance(sub, ast.Name) else (
            sub.attr if isinstance(sub, ast.Attribute) else "")
        if "decode" in ident.lower() and "kernel" in ident.lower():
            return True
    return False


@rule("R23")
def r23_one_decode_kernel(tree: ast.AST, lines: List[str],
                          path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R23_SCOPE) \
            or any(part in norm for part in _R23_EXEMPT):
        return []

    def annotated(ln: int) -> bool:
        return any(_R23_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if _call_name(node).rsplit(".", 1)[-1] != "pallas_call":
            continue
        kernel = node.args[0] if node.args else None
        if kernel is None:
            for kw in node.keywords:
                if kw.arg in ("kernel", "f"):
                    kernel = kw.value
        if kernel is None or not _r23_mentions_decode(kernel):
            continue
        if annotated(node.lineno):
            continue
        out.append(_finding(
            "R23", path, lines, node,
            "decode-attention `pallas_call` constructed outside the "
            "unified dispatcher (ops/paged_attention.py) — PR 18 "
            "collapsed the decode kernels into one ragged kernel "
            "because per-site forks skip the stale-tail zeroing and "
            "int8 scale-folding defenses and drift on the next "
            "geometry change",
            "dispatch through ops/paged_attention.py, or annotate "
            "with `# dynalint: kernel-ok=<why this copy must exist — "
            "e.g. frozen parity oracle>` within three lines above"))
    return out


# -- R24: hedged dispatch is only exact pre-commit ----------------------------

# Scope: the dynamo_tpu package and tools/ (a load-shedding driver or
# a future router layer is exactly where a "just hedge it" call gets
# added). The fail-slow PR (ISSUE 19) made hedged dispatch exact by
# CONSTRUCTION: a hedge may only fire while zero tokens are committed
# (identical request + deterministic engines => identical tokens, so
# whichever stream wins, the client sees one token sequence), the
# first frame wins the race, and the loser is cancelled through the
# abort path. Every one of those three legs is load-bearing — hedge
# after commit duplicates tokens the client already consumed; no
# cancellation leaks a stream and double-charges the fleet. Lexical
# like R22: the enclosing function must write the race discipline
# down, or the call carries `# dynalint: hedge-ok=<reason>` within
# three lines above. frontend/reliability.py owns the reference race
# and stays in scope on purpose (the R23 oracle-module precedent): its
# call site speaks the vocabulary, so a second undisciplined site
# still flags.
_R24_SCOPE = ("dynamo_tpu/", "tools/")
_R24_TERMINALS = {"start_hedge", "_start_hedge", "dispatch_hedge",
                  "_dispatch_hedge", "hedge_dispatch"}
_R24_ANNOT_RE = re.compile(r"#\s*dynalint:\s*hedge-ok=\S+")
# the vocabulary is the exactness discipline itself: who wins, who is
# cancelled, and why committed tokens fence the hedge out. Bare
# "hedge" must NOT satisfy the rule — every call site spells that.
_R24_HANDLED_RE = re.compile(
    r"first[-_ ]?(?:frame|token)?[-_ ]?win|pre[-_ ]?commit|"
    r"\bcancel|abandon|loser|uncommitted|zero +tokens +committed",
    re.I)


@rule("R24")
def r24_hedged_dispatch_exactness(tree: ast.AST, lines: List[str],
                                  path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R24_SCOPE) \
            or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R24_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R24_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name.rsplit(".", 1)[-1] not in _R24_TERMINALS:
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R24", path, lines, node,
            f"`{name}(...)` dispatches a hedge attempt without "
            "referencing the first-wins / loser-cancellation / "
            "pre-commit discipline — a hedge is only exact while ZERO "
            "tokens are committed; a call site that can't point at "
            "the race rules is where a refactor fires a hedge after "
            "commit (duplicating tokens the client already consumed) "
            "or leaks the losing stream",
            "state (docstring/comment) the race discipline — e.g. "
            "'first frame wins; loser cancelled via abort; suppressed "
            "once any token is committed' — or annotate with "
            "`# dynalint: hedge-ok=<why exactness holds here>`"))
    return out


# -- R25: streamed window-pool claim/fill/victim discipline -------------------

# Scope: dynamo_tpu/ + tools/ (a streaming driver or a future "just
# stage the page" helper is where an undisciplined site gets added).
# The million-token streaming PR made decode-beyond-HBM exact by
# CONSTRUCTION: window-pool halves are KEYED by the segment's chained
# page hashes (a stale prefetch against a changed cold set can never
# be consumed), every cold fetch pays the traveling-checksum verify
# (rot quarantines the entry and recomputes ONLY the victim page), and
# spill victims ride the checksummed offload leg — the bytes that come
# back are the bytes that left. Lexical like R24: the enclosing
# function must write that discipline down, or the call carries
# `# dynalint: stream-ok=<reason>` within three lines above.
# engine/streaming.py owns the reference loop and stays in scope (the
# R23/R24 oracle-module precedent): its sites speak the vocabulary, so
# a second undisciplined claim/fill/victim site still flags.
_R25_SCOPE = ("dynamo_tpu/", "tools/")
_R25_TERMINALS = {"_assemble", "_spill_victims", "_pin_cold"}
_R25_QUALIFIED = {("pool", "take"), ("pool", "prefetch")}
_R25_ANNOT_RE = re.compile(r"#\s*dynalint:\s*stream-ok=\S+")
# the vocabulary is the exactness discipline itself: the keyed double
# buffer, the verify/quarantine gate, and the chained-hash/checksum
# custody of spilled bytes. Bare "stream"/"page"/"spill"/"victim" must
# NOT satisfy the rule — `_spill_victims` spells the last two itself.
_R25_HANDLED_RE = re.compile(
    r"double.?buffer|prefetch\s+(?:hit|late)|stale\s+prefetch|"
    r"checksum|chain(?:ed|ing)\s+hash|quarantin|verify",
    re.I)


@rule("R25")
def r25_stream_window_pool_discipline(tree: ast.AST, lines: List[str],
                                      path: str) -> List[Finding]:
    norm = path.replace("\\", "/")
    if not any(part in norm for part in _R25_SCOPE) \
            or "tests/" in norm:
        return []

    def annotated(ln: int) -> bool:
        return any(_R25_ANNOT_RE.search(_line(lines, x))
                   for x in range(ln - 3, ln + 1))

    funcs = [n for n in ast.walk(tree)
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def enclosing_handles(ln: int) -> bool:
        inner = None
        for fn in funcs:
            end = getattr(fn, "end_lineno", fn.lineno)
            if fn.lineno <= ln <= end and (
                    inner is None or fn.lineno >= inner.lineno):
                inner = fn
        if inner is None:
            lo, hi = max(1, ln - 10), min(len(lines), ln + 10)
        else:
            lo, hi = inner.lineno, getattr(inner, "end_lineno",
                                           inner.lineno)
        return any(_R25_HANDLED_RE.search(_line(lines, x))
                   for x in range(lo, hi + 1))

    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        parts = name.split(".")
        if parts[-1] not in _R25_TERMINALS and \
                tuple(parts[-2:]) not in _R25_QUALIFIED:
            continue
        if annotated(node.lineno) or enclosing_handles(node.lineno):
            continue
        out.append(_finding(
            "R25", path, lines, node,
            f"`{name}(...)` claims/fills/spills a streamed window-pool "
            "page without referencing the keyed-double-buffer / "
            "verify-on-fetch / checksummed-spill discipline — streamed "
            "decode is only exact while stale prefetches can't be "
            "consumed (hash-tuple keys), rot quarantines and recomputes "
            "the victim page, and spilled bytes ride the checksummed "
            "offload leg; a site that can't point at those rules is "
            "where a refactor consumes a stale half or spills an "
            "unverifiable page",
            "state (docstring/comment) the discipline — e.g. 'double "
            "buffer keyed by page hashes; rot quarantines + recomputes "
            "the victim; spills ride the checksummed offload leg' — or "
            "annotate with `# dynalint: stream-ok=<why exactness holds "
            "here>`"))
    return out


# -- R21: await-interleaving TOCTOU (layer 3) ---------------------------------

# The detector lives in interleave.py (it is a dataflow analysis over
# the flow.py CFG, not a lexical matcher); importing it here registers
# it so run_rules / the runner see one rule table.
from dynamo_tpu.analysis.interleave import (  # noqa: E402
    r21_await_interleaving_toctou,
)

RULES["R21"] = r21_await_interleaving_toctou


def run_rules(tree: ast.AST, lines: List[str], path: str) -> List[Finding]:
    findings: List[Finding] = []
    for rid in sorted(RULES):
        findings.extend(RULES[rid](tree, lines, path))
    return findings
