"""Benchmark: decode throughput of the native JAX engine on a TPU.

Prints ONE JSON line on stdout: {"metric", "value", "unit", "vs_baseline",
"device", "extras"}; everything else goes to stderr.

Measures steady-state decode throughput (tokens/sec/chip) of the llama3-1b
flagship under continuous batching with all slots busy — the serving-side
analogue of the reference's throughput/GPU headline (BASELINE.md). The
reference publishes no machine-readable numbers (BASELINE.json.published={});
vs_baseline is measured against NOMINAL_BASELINE below: a
bandwidth-roofline estimate for this model on one v5e chip
(~2.5 GB of bf16 weights re-read per token; v5e HBM BW 819 GB/s
=> ~330 steps/s ceiling; at batch 8 with overheads a strong serving stack
lands near ~40% of roofline). vs_baseline > 1.0 means we beat that.

One process runs the phases in order (it holds the chip; it starts no
child that needs one). What it refuses to paper over:

- the flagship on a backend other than TPU is an error: no result line,
  exit 2. BENCH_MODEL=tiny on the CPU validates that every phase still
  runs; its line says so (unit, device) and carries no vs_baseline — the
  nominal baseline is a v5e roofline figure and a CPU rate is not a
  device metric;
- on a TPU the Pallas kernel probe either passes or fails the run;
- a phase that raises is recorded as extras[<phase>] = {"failure": ...},
  the later phases still run, and the exit code is nonzero.

What is measured and how (best chunk, the nominal baseline, the BENCH_*
variables) is unchanged and is the benchmark PR's to replace.
"""
import json
import os
import sys
import time
import traceback
from typing import Optional

NOMINAL_BASELINE_TOK_S = 1000.0  # ~40% of single-chip roofline at batch 8
METRIC = "decode_tokens_per_sec_per_chip_llama3_1b_bf16_b8"
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "540"))  # optional phases
#                         are skipped once this much of the run is spent
HERE = os.path.dirname(os.path.abspath(__file__))


def metric_name() -> str:
    """Metric name for the current env (BENCH_MODEL/BENCH_QUANT)."""
    name = METRIC
    model = os.environ.get("BENCH_MODEL", "llama3-1b")
    if model != "llama3-1b":
        name = f"decode_tokens_per_sec_per_chip_{model}_b8_validation"
    quant = os.environ.get("BENCH_QUANT", "")
    if quant:
        if quant != "int8":
            # fail before anything runs, so a typo'd quant can never stamp
            # an artifact labeled with a configuration that was rejected,
            # not measured
            raise SystemExit(f"BENCH_QUANT={quant!r} unsupported "
                             "(supported: int8)")
        # the flagship name carries the dtype: swap it rather than emit
        # a self-contradictory "..._bf16_b8_int8" label (validation names
        # carry no dtype — append there)
        name = (name.replace("_bf16_", f"_{quant}_")
                if "_bf16_" in name else f"{name}_{quant}")
    return name


T0 = time.time()


def log(*a):
    print(f"[bench +{time.time() - T0:7.1f}s]", *a, file=sys.stderr,
          flush=True)


def trajectory_row(result: dict, run_id: Optional[str] = None) -> dict:
    """Normalize one bench result into the BENCH_TRAJECTORY.jsonl row
    shape tools/bench_compare.py consumes: metric/value/unit plus a
    bounded extras subset (full extras stay in the per-run artifact).
    A row with value <= 0 records an infrastructure-failed capture
    (extras.failure carries the fingerprint) — the regression gate
    skips those; they are evidence of the environment, not of the code."""
    extras = result.get("extras") or {}
    keep = {k: extras[k] for k in ("failure", "quant", "kernel",
                                   "decode_steps", "parity")
            if k in extras}
    return {
        "run_id": run_id or os.environ.get(
            "BENCH_RUN_ID",
            time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())),
        "metric": result.get("metric"),
        "value": float(result.get("value") or 0.0),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "extras": keep,
    }



def append_trajectory(best: dict, platform: str) -> None:
    """Normalized trajectory rows (tools/bench_compare.py gates on these):
    one append-only JSONL record per run plus the derived ratio rows,
    under the tools/artifacts.py policy. BENCH_TRAJECTORY names the file
    (default BENCH_TRAJECTORY.jsonl beside this script; "0" disables, for
    CPU validation scratch runs); BENCH_RUN_ID labels the rows. Ratio
    metrics are suffixed by model + the platform that ran them, so a tiny
    CPU validation row can never be scored against a TPU gate."""
    traj = os.environ.get("BENCH_TRAJECTORY",
                          os.path.join(HERE, "BENCH_TRAJECTORY.jsonl"))
    if traj == "0":
        return
    from tools.artifacts import append_jsonl
    append_jsonl(traj, trajectory_row(best))
    log(f"trajectory row -> {traj}")
    run_id = os.environ.get(
        "BENCH_RUN_ID", time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    suffix = "{}_{}".format(
        os.environ.get("BENCH_MODEL", "llama3-1b").replace("-", "_"),
        "tpu" if platform == "tpu" else "cpu")
    to = best["extras"].get("transfer_overlap") or {}
    wp = best["extras"].get("warm_prefix") or {}
    if "failure" in wp:
        wp = {}
    sh = best["extras"].get("sharded_transfer") or {}
    if "failure" in sh:
        sh = {}
    dk = best["extras"].get("decode_kernel") or {}
    if "failure" in dk:
        dk = {}
    lc = best["extras"].get("long_context") or {}
    if "failure" in lc:
        lc = {}
    ratios = {
        f"disagg_agg_ttft_ratio_early_{suffix}":
            to.get("disagg_agg_ttft_ratio_early")
            if "failure" not in to else None,
        f"disagg_decode_gain_{suffix}":
            best["extras"].get("disagg_decode_gain"),
        # warm-prefix ladder (ISSUE 13): cross-worker
        # pool-fetch TTFT over cold, and prefetch over fetch
        # — both gated "lower" in BASELINE.json
        f"warm_prefix_pool_fetch_ttft_ratio_{suffix}":
            wp.get("pool_fetch_cold_ttft_ratio"),
        f"warm_prefix_prefetch_fetch_ttft_ratio_{suffix}":
            wp.get("prefetch_fetch_ttft_ratio"),
        # remote-pool rungs (ISSUE 17): cross-HOST replica-
        # walk fetch TTFT over cold must stay under the cold
        # ceiling — both gated "lower" in BASELINE.json
        f"warm_prefix_remote_fetch_ttft_ratio_{suffix}":
            wp.get("remote_fetch_cold_ttft_ratio"),
        f"warm_prefix_remote_prefetch_ttft_ratio_{suffix}":
            wp.get("remote_prefetch_fetch_ttft_ratio"),
        # sharded parallel transfer (ISSUE 15): N-stream /
        # 1-stream wall time under per-host-NIC pacing, and
        # the disagg TTFT ratio — both gated "lower"
        f"sharded_transfer_wall_ratio_{suffix}":
            sh.get("paced_wall_ratio"),
        f"sharded_disagg_ttft_ratio_{suffix}":
            sh.get("disagg_ttft_ratio"),
        # ragged kernel (ISSUE 18): unified/legacy step time
        # must stay at or under parity — gated "lower"
        f"decode_kernel_unified_legacy_step_ratio_{suffix}":
            dk.get("unified_legacy_step_ratio"),
        # long-context streaming (ISSUE 20): the ITL price
        # of attending beyond HBM at the 4x-budget rung,
        # token-identity-gated at capture — gated "lower"
        f"long_context_itl_inflation_4x_{suffix}":
            lc.get("itl_inflation_4x"),
    }
    for metric, value in ratios.items():
        if value and value > 0:
            append_jsonl(traj, {
                "run_id": run_id, "metric": metric,
                "value": float(value), "unit": "ratio",
                "vs_baseline": None, "extras": {}})
            log(f"trajectory row [{metric}={value}] -> {traj}")


class Run:
    """The run's one result line, the phase it is in, and what failed."""

    def __init__(self):
        self.result = {"metric": metric_name(), "value": 0.0,
                       "unit": "tokens/s/chip", "vs_baseline": 0.0,
                       "extras": {}}
        self.phase = "import"
        self.failed = []

    def set_phase(self, phase):
        self.phase = phase

    def record(self, tok_s: float, n_chips: int):
        value = tok_s / max(1, n_chips)
        self.result["value"] = round(value, 2)
        if self.result["vs_baseline"] is not None:
            self.result["vs_baseline"] = round(
                value / NOMINAL_BASELINE_TOK_S, 3)

    def evidence(self, key: str, what: str, fn) -> None:
        """Run one optional evidence phase into extras[key]. A failure is
        recorded, fails the run's exit code, and the next phase still
        runs."""
        try:
            self.result["extras"][key] = fn()
        except Exception as e:
            log(f"{what} failed ({type(e).__name__}: {e})")
            traceback.print_exc(file=sys.stderr)
            self.result["extras"][key] = {"failure": str(e)}
            self.failed.append(self.phase)


# THE measurement engine geometry — one literal shared by the worker's
# EngineConfig and run_parity's fresh-build/twin configs, so the parity
# check can never silently compare engines built from diverging configs
PAGE_KWARGS = dict(
    page_size=64, num_pages=256, max_slots=8, max_prefill_chunk=128,
    prefill_buckets=(128,), max_model_len=2048, max_prefill_batch=8)

# kv_quant parity gate thresholds (ONE definition — tests/test_kv_quant.py
# and tools/tpu_parity_quick.py both import these, so the committed gate
# and the TPU ladder can never drift apart): the logit drift must stay
# under atol + rtol * max|logit| (per-row int8 error is ~0.4% relative;
# the bound leaves ~10x headroom so only a real codec bug trips it),
# and the DECISIVE greedy-match rate — argmax agreement at positions
# whose reference top-2 margin exceeds 2x the drift bound, i.e. where a
# bounded perturbation could never legitimately flip the choice — must
# be >= KVQ_MATCH_MIN. Near-tie positions (margin <= 2x bound) are
# reported in the raw rate but not gated: any epsilon perturbation
# flips them by definition (the §3b bf16 caveat, docs/PERF.md).
KVQ_MATCH_MIN = 0.99
KVQ_DRIFT_RTOL = 0.05
KVQ_DRIFT_ATOL = 0.05


def run_kv_quant_parity(model_cfg, engine_kwargs=None, n_tokens=64,
                        n_prompts=3, logf=None):
    """kv_quant="int8" exactness gate: TEACHER-FORCED greedy-match rate
    vs the unquantized twin plus bounded logit drift.

    ONE implementation shared by the tier-1 gate (tests/test_kv_quant.py)
    and the TPU ladder (tools/tpu_parity_quick.py with
    PARITY_KV_QUANT=int8), so the committed thresholds are exactly what
    runs on hardware.

    Why teacher-forced: on a free-running greedy stream, ONE near-tie
    argmax flip permanently diverges the context and every later token
    "mismatches" — the rate then measures butterfly effects, not codec
    error (observed: a single flip at token 2 of a 64-token tiny-model
    stream scored 0.05). Instead the reference engine free-runs
    n_tokens greedily, and both representations replay the SAME
    (prompt + reference continuation) through one prefill-shaped
    forward over shared params; the match rate is per-POSITION argmax
    agreement at every decision point — exactly "how often does int8
    KV flip a greedy decision", cascade-free. Drift is the max abs
    logit delta over the same decision points, bounded by
    KVQ_DRIFT_ATOL + KVQ_DRIFT_RTOL * max|logit|.

    Returns a verdict dict: {pass, greedy_match_rate, max_logit_drift,
    drift_bound, n_tokens, per_prompt}.
    """
    import dataclasses

    import numpy as np

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    logf = logf or log
    kw = dict(engine_kwargs or PAGE_KWARGS)
    pmod = min(1000, model_cfg.vocab_size - 2)
    prompts = [[(31 * j + 97 * i) % pmod + 1 for j in range(48)]
               for i in range(n_prompts)]
    params = SamplingParams(max_tokens=n_tokens, temperature=0.0,
                            ignore_eos=True)

    # teacher streams from the REAL unquantized engine (the serving path
    # writes/reads its pages exactly as deployed)
    ref_eng = NativeEngine(model_cfg, EngineConfig(**kw), seed=0)
    refs = [ref_eng.generate(p, params, f"kvq-ref-{i}")
            for i, p in enumerate(prompts)]
    del ref_eng  # free HBM before the replay forwards

    import jax
    import jax.numpy as jnp

    from dynamo_tpu.models import llama
    from dynamo_tpu.models.llama import AttnMetadata
    cfg_q = dataclasses.replace(model_cfg, kv_quant="int8")
    ps = kw.get("page_size", 64)
    prm = llama.init_params(jax.random.PRNGKey(0), model_cfg)

    def replay_logits(cfg, seq):
        """One prefill-shaped forward over the whole teacher sequence:
        pages are written (quantized under cfg_q) and read back by the
        chunk's own causal attention — the codec round-trip at every
        position."""
        t = len(seq)
        n_pages_row = -(-t // ps)
        meta = AttnMetadata(
            positions=jnp.asarray([list(range(t))], jnp.int32),
            page_table=jnp.asarray([list(range(n_pages_row))], jnp.int32),
            kv_lens=jnp.asarray([t], jnp.int32),
            write_idx=jnp.asarray([list(range(t))], jnp.int32))
        cache = llama.init_cache(cfg, n_pages_row, ps)
        lg = jax.jit(lambda p, c: llama.forward(
            p, cfg, jnp.asarray([seq], jnp.int32), c, meta)[0])(prm, cache)
        return np.asarray(lg[0], np.float32)

    rows = []   # (margins, agree, drift_row_max, |logit| max) per prompt
    for prompt, ref in zip(prompts, refs):
        seq = list(prompt) + list(ref)
        lg_ref = replay_logits(model_cfg, seq)
        lg_q = replay_logits(cfg_q, seq)
        # decision points: positions that predicted each generated token
        lo, hi = len(prompt) - 1, len(seq) - 1
        a = lg_ref[lo:hi]
        agree = a.argmax(axis=-1) == lg_q[lo:hi].argmax(axis=-1)
        top2 = np.sort(a, axis=-1)[:, -2:]
        rows.append((top2[:, 1] - top2[:, 0], agree,
                     float(np.abs(lg_q[lo:hi] - a).max()),
                     float(np.abs(a).max())))
    del prm
    drift = max(r[2] for r in rows)
    bound = KVQ_DRIFT_ATOL + KVQ_DRIFT_RTOL * max(r[3] for r in rows)
    margins = np.concatenate([r[0] for r in rows])
    agree = np.concatenate([r[1] for r in rows])
    total = len(agree)
    raw_rate = float(agree.mean()) if total else 1.0
    # decisive positions: the top-2 margin exceeds what a bound-respecting
    # perturbation could ever flip (top1 loses <= bound, runner-up gains
    # <= bound). A flip HERE is a codec bug, not a near-tie.
    decisive = margins > 2 * bound
    dec_rate = (float(agree[decisive].mean()) if decisive.any() else 1.0)
    per_prompt = [round(float(r[1].mean()), 4) for r in rows]
    ok = dec_rate >= KVQ_MATCH_MIN and drift <= bound
    logf(f"kv_quant parity (teacher-forced): decisive greedy match "
         f"{dec_rate:.4f} over {int(decisive.sum())}/{total} decisive "
         f"positions (min {KVQ_MATCH_MIN}; raw incl. near-ties "
         f"{raw_rate:.4f}), logit drift {drift:.4f} (bound {bound:.4f}) "
         f"-> {'OK' if ok else 'FAIL'}")
    return {"pass": ok, "greedy_match_rate": round(dec_rate, 4),
            "raw_match_rate": round(raw_rate, 4),
            "decisive_positions": int(decisive.sum()),
            "max_logit_drift": round(drift, 5),
            "drift_bound": round(bound, 5), "n_tokens": total,
            "per_prompt": per_prompt}


def run_kv_quant_ab(model_cfg, base_kwargs=None, *, seconds=10.0,
                    n_chips=1, logf=None):
    """kv_quant A/B evidence for extras["kv_quant"]: capacity at a fixed
    HBM page-byte budget + an int8-KV churn pass.

    Capacity phase: both modes get the SAME HBM byte budget (the bf16
    geometry's page bytes x num_pages); int8 pages are ~half the bytes
    (+ scale rows), so the int8 allocator holds ~1.9x the pages and the
    measured concurrent-slot count — churn-shaped requests admitted via
    a bare Scheduler until allocation fails — shows the capacity
    multiplier directly (no device work; the allocator IS the resource).

    Churn phase: the PR-5 churn machinery shape (staggered decode
    budgets, replacement arrivals, mixed scheduler) on a kv_quant="int8"
    engine — CPU validation proves the plumbing; the TPU ladder item
    (BENCH_SELF_r06_kvq) gives the hardware verdict.
    """
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import (
        EngineRequest, SamplingParams, Scheduler,
    )
    from dynamo_tpu.ops.kv_quant import page_bytes

    logf = logf or log
    kw = dict(base_kwargs or PAGE_KWARGS)
    import jax.numpy as jnp
    itemsize = jnp.dtype(model_cfg.dtype).itemsize
    pb_ref = page_bytes(model_cfg.num_layers, model_cfg.num_kv_heads,
                        kw["page_size"], model_cfg.head_dim, itemsize,
                        False)
    pb_q = page_bytes(model_cfg.num_layers, model_cfg.num_kv_heads,
                      kw["page_size"], model_cfg.head_dim, itemsize, True)
    budget = kw["num_pages"] * pb_ref

    def max_slots_at(num_pages):
        """Churn-shaped admissions (isl 4x128, decode budget 64) into a
        bare scheduler until a request cannot get pages."""
        # alternating scheduler with unbounded prefill priority: every
        # plan is a pure PrefillPlan (decode never runs), so the commit
        # loop below only needs commit_prefill_row and no request ever
        # finishes and releases pages mid-measurement
        from dynamo_tpu.engine.scheduler import PrefillPlan
        c = EngineConfig(**{**kw, "num_pages": num_pages,
                            "max_slots": 4096, "mixed_token_budget": 0,
                            "max_prefill_streak": 0})
        s = Scheduler(c)
        isl, count = 512, 0
        pmod = min(1000, model_cfg.vocab_size - 2)
        while count < 4096:
            rid = f"cap-{count}"
            s.add_request(EngineRequest(
                rid, [(7 * count + 3 * j) % pmod + 1 for j in range(isl)],
                SamplingParams(max_tokens=64, ignore_eos=True)))
            # drive this request's prefill to completion so its pages are
            # truly held (admission-time allocation covers isl+64); any
            # non-prefill plan (decode-only progress) or MemoryError means
            # the waiting request is page-blocked — capacity reached
            done = False
            while not done:
                try:
                    plan = s.schedule()
                except MemoryError:
                    plan = None
                if plan is None or not isinstance(plan, PrefillPlan):
                    break
                for i in reversed(range(len(plan.seqs))):
                    if plan.seqs[i] is None:
                        continue
                    tok = s.commit_prefill_row(
                        plan, i, 9 if plan.is_last_chunk[i] else None)
                    done = done or tok is not None
            if not done:
                break
            count += 1
        return count

    slots_ref = max_slots_at(budget // pb_ref)
    slots_q = max_slots_at(budget // pb_q)
    capacity = {
        "hbm_page_budget_bytes": budget,
        "page_bytes_bf16": pb_ref, "page_bytes_int8": pb_q,
        "page_bytes_ratio": round(pb_ref / pb_q, 3),
        "slots_bf16": slots_ref, "slots_int8": slots_q,
        "slot_ratio": round(slots_q / max(1, slots_ref), 3),
    }
    logf(f"kv_quant capacity at {budget >> 20} MiB page budget: "
         f"{slots_ref} bf16 slots vs {slots_q} int8 slots "
         f"({capacity['slot_ratio']}x); bytes/page {pb_ref} -> {pb_q} "
         f"({capacity['page_bytes_ratio']}x)")

    # churn pass on the int8 engine (PR-5 machinery shape)
    eng = NativeEngine(model_cfg, EngineConfig(kv_quant="int8", **kw),
                       seed=0)
    slots = kw["max_slots"]
    pmod = min(1000, model_cfg.vocab_size - 2)
    prompt_len = 128
    # churn ISL targets the 4x long-ISL shape but clamps so all slots'
    # admission-time allocations (isl + the largest staggered budget)
    # fit in ~80% of the page budget (tiny CPU validation configs are
    # much smaller than the TPU geometry)
    ps = kw["page_size"]
    fit = (int(0.8 * kw["num_pages"]) // slots) * ps - 88
    churn_isl = max(ps, min(4 * prompt_len, fit))
    next_id = [0]

    def add_fresh():
        salt = 977 * (next_id[0] + 1)
        eng.add_request(EngineRequest(
            f"kvq-churn-{next_id[0]}",
            [(salt + 3 * j) % pmod + 1 for j in range(churn_isl)],
            SamplingParams(max_tokens=48 + (next_id[0] % 5) * 8,
                           temperature=0.0, ignore_eos=True)))
        next_id[0] += 1

    for _ in range(slots):
        add_fresh()
    warm_finishes = 0
    for _ in range(600):
        for ev in eng.step():
            if ev.finished:
                add_fresh()
                warm_finishes += 1
        if warm_finishes >= slots:
            break
    t0 = _time.perf_counter()
    tokens = 0
    while _time.perf_counter() < t0 + seconds:
        for ev in eng.step():
            if ev.token is not None:
                tokens += 1
            if ev.finished:
                add_fresh()
    tok_s = tokens / (_time.perf_counter() - t0) / max(1, n_chips)
    logf(f"kv_quant churn (int8 pages, mixed scheduler): "
         f"{tok_s:.1f} tok/s/chip")
    del eng
    return {"capacity": capacity,
            "churn_int8_tok_s": round(tok_s, 1)}


def run_decode_kernel_ab(model_cfg, base_kwargs=None, *, rows=8,
                         n_chips=1, logf=None):
    """Ragged-kernel A/B for extras["decode_kernel"] (ISSUE 18): step
    time of the frozen pre-PR-18 kernel vs the unified ragged kernel,
    token-identity enforced in-phase.

    Each arm is ONE jitted "decode step" at the model's geometry:
    paged attention over ragged lengths -> a head projection -> the
    sampling tail. Arms: (a) legacy (s, hkv)-grid kernel, (b) unified
    ragged kernel. Both must sample IDENTICAL tokens (top_p = 1
    workload); the unified/legacy step-time ratio is the tentpole's
    no-regression gate (<= 1.0, BASELINE.json
    `decode_kernel_unified_legacy_step_ratio_*`). CPU runs both
    kernels in interpret mode (program-count overhead dominates: the
    ragged kernel launches s programs vs the legacy s*hkv); the TPU
    ladder item (BENCH_SELF_r18_ragged_tpu) gives the hardware verdict.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.engine import sampler
    from dynamo_tpu.ops.paged_attention import decode_paged_attention
    from dynamo_tpu.ops.paged_attention_oracle import (
        decode_paged_attention_legacy,
    )

    logf = logf or log
    kw = dict(base_kwargs or PAGE_KWARGS)
    interpret = jax.devices()[0].platform != "tpu"
    s = rows
    h, hkv, hd = (model_cfg.num_heads, model_cfg.num_kv_heads,
                  model_cfg.head_dim)
    ps, pb = kw["page_size"], 4
    p = s * pb
    vocab = model_cfg.vocab_size
    rng = np.random.default_rng(18)
    q = jnp.asarray(rng.standard_normal((s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, p, ps, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, p, ps, hd)), jnp.float32)
    pt = jnp.asarray(np.arange(s * pb).reshape(s, pb), jnp.int32)
    lens = jnp.asarray(rng.integers(1, pb * ps, s), jnp.int32)
    w_head = jnp.asarray(
        rng.standard_normal((h * hd, vocab)) * 0.05, jnp.float32)
    temp = jnp.full((s,), 0.8, jnp.float32)
    top_k = jnp.full((s,), 40, jnp.int32)
    top_p = jnp.ones((s,), jnp.float32)
    keys = sampler.make_keys(jnp.arange(s, dtype=jnp.int32),
                             jnp.zeros((s,), jnp.int32))

    def make_step(kernel):
        def f(q, k, v, pt, lens, w_head, temp, top_k, top_p, keys):
            attn = kernel(q, k, v, pt, lens, interpret=interpret)
            logits = attn.reshape(s, h * hd) @ w_head
            return sampler.sample(logits, temp, top_k, top_p, keys)
        return jax.jit(f)

    arms = {
        "legacy": make_step(decode_paged_attention_legacy),
        "unified": make_step(decode_paged_attention),
    }
    args = (q, k, v, pt, lens, w_head, temp, top_k, top_p, keys)
    toks, ms = {}, {}
    reps = 30 if not interpret else 4
    for name, fn in arms.items():
        toks[name] = np.asarray(fn(*args))     # compile + identity probe
        t0 = _time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        out.block_until_ready()
        ms[name] = (_time.perf_counter() - t0) / reps * 1e3
    identical = bool(np.array_equal(toks["legacy"], toks["unified"]))
    # token identity is the phase's correctness gate, not a soft metric
    assert identical, {k2: v2.tolist() for k2, v2 in toks.items()}
    res = {
        "rows": s, "heads": h, "kv_heads": hkv, "head_dim": hd,
        "page_size": ps, "interpret": interpret,
        "legacy_step_ms": round(ms["legacy"], 3),
        "unified_step_ms": round(ms["unified"], 3),
        "unified_legacy_step_ratio": round(
            ms["unified"] / ms["legacy"], 4) if ms["legacy"] else None,
        "tokens_identical": identical,
    }
    logf(f"decode kernel A/B ({'interpret' if interpret else 'tpu'}): "
         f"legacy {ms['legacy']:.2f} ms -> unified {ms['unified']:.2f} ms "
         f"(ratio {res['unified_legacy_step_ratio']}); tokens identical")
    return res


def run_transfer_overlap_ab(model_cfg, base_kwargs=None, *, requests=6,
                            warm=2, n_chips=1,
                            logf=None):
    """Disagg TTFT A/B for extras["transfer_overlap"] (ISSUE 11):

    1. aggregated TTFT — the same decode worker prefills locally
       (disagg router threshold lifted), the matched-load denominator;
    2. disagg wait-for-final-chunk — early_decode off: TTFT pays
       prefill + FULL transfer + completion notify;
    3. disagg early-decode — the first token goes out the moment the
       prefill samples it, decode gates on the committed frontier.

    All three run on the SAME in-process stack (MemoryPlane control
    plane, real KvTransferServer/RemoteTransferBackend over TCP
    loopback, two engines sharing the backend) with distinct prompts
    per request so the prefix cache can't fake a TTFT. Also folds in a
    small seeded routing A/B (runtime/simcluster.py routing_ab —
    prefix-only vs transfer-aware p99 over heterogeneous links; the
    committed full-scale run is ROUTING_AB_r11.json). CPU validation
    proves the plumbing and ratio direction; the TPU ladder item
    (BENCH_SELF_r11_overlap) gives the hardware verdict."""
    import asyncio

    from dynamo_tpu.disagg import (
        DisaggDecodeWorker, DisaggregatedRouter, KvTransferServer,
        PrefillQueue, PrefillWorker, RemoteTransferBackend,
    )
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.llm.worker import NativeEngineWorker
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.transports.memory import MemoryPlane

    logf = logf or log
    kw = dict(base_kwargs or PAGE_KWARGS)
    pmod = min(1000, model_cfg.vocab_size - 2)
    ps = kw["page_size"]
    # several transfer chunks per request, bounded so two requests'
    # admission-time allocations fit the page budget comfortably
    prompt_len = max(2 * ps, min(4 * 128, (kw["num_pages"] // 4) * ps - ps))
    max_tokens = 4

    async def main():
        plane = MemoryPlane()
        queue = PrefillQueue(plane.messaging, "bench", "overlap")
        drouter = DisaggregatedRouter(max_local_prefill_length=ps,
                                      max_prefill_queue_size=64,
                                      model="bench")
        decode = DisaggDecodeWorker(
            NativeEngine(model_cfg, EngineConfig(**kw), seed=0),
            plane.messaging, drouter, queue, worker_id="bench-dec",
            prefill_timeout_s=300.0)
        server = await KvTransferServer(decode, "bench-dec").start()
        await server.register(plane.kv)
        transfer = RemoteTransferBackend(plane.kv, chunk_pages=2,
                                         window_chunks=2)
        prefill = PrefillWorker(
            NativeEngineWorker(NativeEngine(model_cfg, EngineConfig(**kw),
                                            seed=0)),
            queue, transfer, plane.messaging)
        await decode.start()
        await prefill.start()
        rid_n = [0]

        async def one_ttft(tag):
            rid_n[0] += 1
            rid = f"ov-{tag}-{rid_n[0]}"
            salt = 131 * rid_n[0] + sum(tag.encode())
            pre = PreprocessedRequest(
                request_id=rid,
                token_ids=[(salt + 3 * j) % pmod + 1
                           for j in range(prompt_len)],
                stop=StopConditions(max_tokens=max_tokens,
                                    ignore_eos=True))
            t0 = time.perf_counter()
            ttft = None
            async for frame in decode.generate(
                    pre.model_dump(exclude_none=True), Context(rid)):
                if ttft is None and frame.get("token_ids"):
                    ttft = time.perf_counter() - t0
            return ttft

        async def mode(tag):
            for _ in range(warm):
                await one_ttft(tag + "w")   # compiles out of the timing
            vals = sorted([await one_ttft(tag) for _ in range(requests)])
            return {"p50_ms": round(vals[len(vals) // 2] * 1e3, 2),
                    "max_ms": round(vals[-1] * 1e3, 2),
                    "mean_ms": round(sum(vals) / len(vals) * 1e3, 2)}

        try:
            saved = drouter.max_local_prefill_length
            drouter.max_local_prefill_length = 1 << 30
            agg = await mode("agg")        # local prefill: the denominator
            drouter.max_local_prefill_length = saved
            decode.early_decode = False
            wait = await mode("wait")
            decode.early_decode = True
            early = await mode("early")
            counters = {
                "remote_prefills": decode.remote_prefills,
                "early_first_emits": decode.early_first_emits,
                "overlap_activations":
                    decode.engine.scheduler.overlap_activations,
                "overlap_fallbacks": decode.overlap_fallbacks,
            }
        finally:
            await prefill.stop()
            await decode.stop()
            await transfer.close()
            await server.stop()
        return agg, wait, early, counters

    agg, wait, early, counters = asyncio.run(main())
    result = {
        "prompt_len": prompt_len, "requests": requests,
        "agg_ttft": agg,
        "disagg_wait_ttft": wait,
        "disagg_early_ttft": early,
        "disagg_agg_ttft_ratio_wait":
            round(wait["p50_ms"] / max(agg["p50_ms"], 1e-9), 3),
        "disagg_agg_ttft_ratio_early":
            round(early["p50_ms"] / max(agg["p50_ms"], 1e-9), 3),
        "early_vs_wait_ttft_gain":
            round(1.0 - early["p50_ms"] / max(wait["p50_ms"], 1e-9), 3),
        **counters,
    }
    logf(f"transfer overlap TTFT p50: agg {agg['p50_ms']}ms, disagg-wait "
         f"{wait['p50_ms']}ms ({result['disagg_agg_ttft_ratio_wait']}x), "
         f"disagg-early {early['p50_ms']}ms "
         f"({result['disagg_agg_ttft_ratio_early']}x agg; "
         f"{result['early_vs_wait_ttft_gain'] * 100:.0f}% vs wait)")
    # seeded routing A/B at smoke scale (the full-scale committed run
    # is ROUTING_AB_r11.json via tools/routing_ab.py)
    try:
        from dynamo_tpu.runtime.simcluster import SimCluster, SimConfig

        async def ab():
            sim = await SimCluster(SimConfig(workers=48, streams=256,
                                             seed=11)).start()
            try:
                return await sim.routing_ab(requests=800)
            finally:
                await sim.stop()

        rab = asyncio.run(ab())
        result["routing_ab"] = {
            "prefix_only_p99_ms": rab["prefix_only"]["ttft_p99_ms"],
            "transfer_aware_p99_ms": rab["transfer_aware"]["ttft_p99_ms"],
            "p99_improvement": rab["p99_improvement"],
        }
        logf(f"routing A/B (48 workers, seeded): p99 "
             f"{rab['prefix_only']['ttft_p99_ms']}ms -> "
             f"{rab['transfer_aware']['ttft_p99_ms']}ms "
             f"({rab['p99_improvement'] * 100:.1f}% better)")
    except Exception as e:   # the TTFT A/B evidence stands on its own
        result["routing_ab"] = {"failure": f"{type(e).__name__}: {e}"}
    return result


def run_sharded_transfer_ab(model_cfg, base_kwargs=None, *, transfers=5,
                            requests=4, n_streams=2, wire_s=0.2,
                            n_chips=1, logf=None):
    """1-stream vs N-stream KV transfer A/B for
    extras["sharded_transfer"] (ISSUE 15): the decode side swaps its
    single KvTransferServer for a ShardedKvTransferGroup (`n_streams`
    per-host endpoints, one chunk-committed stream per (shard, host))
    and the same transfers re-run.

    Two legs, one in-process stack (MemoryPlane + real TCP loopback):

    1. transfer WALL time — the same extracted page stack shipped
       `transfers` times per mode, with each destination-host link
       paced at a fixed per-NIC bandwidth (sized so one stream's wire
       time is `wire_s`); N parallel streams ride N host NICs, so the
       paced ratio measures whether the data plane actually runs the
       streams CONCURRENTLY end-to-end (a protocol that serialized
       them anywhere — a shared lock, a shared frontier, ack coupling
       — would show ~1.0). The RAW loopback ratio is also recorded but
       NOT gated: one host's event loop and memory bus are shared by
       every stream, so single-host loopback has no parallel NIC to
       win on (same CPU-scale caveat as the churn phase, PERF.md §3b);
       the hardware verdict is the TPU ladder item.
    2. disagg TTFT — full worker stack (wait-for-completion mode, so
       TTFT pays the whole transfer), same per-NIC pacing, p50 over
       `requests` distinct-prompt requests per mode; greedy AND
       seeded-sampled outputs must be token-identical across modes and
       to the local-prefill oracle."""
    import asyncio

    from dynamo_tpu.disagg import (
        DisaggDecodeWorker, DisaggregatedRouter, KvTransferServer,
        PrefillQueue, PrefillWorker, RemoteTransferBackend,
        ShardedKvTransferGroup,
    )
    from dynamo_tpu.disagg.remote_transfer import transfer_key
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
    from dynamo_tpu.llm.worker import NativeEngineWorker
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, StopConditions,
    )
    from dynamo_tpu.runtime.engine import Context
    from dynamo_tpu.runtime.transports.memory import MemoryPlane

    logf = logf or log
    kw = dict(base_kwargs or PAGE_KWARGS)
    pmod = min(1000, model_cfg.vocab_size - 2)
    ps = kw["page_size"]
    prompt_len = max(2 * ps, min(4 * 128, (kw["num_pages"] // 4) * ps - ps))
    max_tokens = 4

    class NicPaced(RemoteTransferBackend):
        """Each destination host's NIC serializes its payload at a
        fixed bandwidth: the write path sleeps frame_bytes/bw per
        chunk, per connection — concurrent streams to different hosts
        pace concurrently, exactly the multi-NIC premise."""

        nic_bytes_per_s = 1e9   # set once the payload size is known

        async def _write(self, writer, frame, deadline):
            await super()._write(writer, frame, deadline)
            nb = sum(len(v) for v in frame.values()
                     if isinstance(v, (bytes, bytearray)))
            if nb:
                await asyncio.sleep(nb / self.nic_bytes_per_s)

    async def main():
        plane = MemoryPlane()
        queue = PrefillQueue(plane.messaging, "bench", "sharded")
        drouter = DisaggregatedRouter(max_local_prefill_length=ps,
                                      max_prefill_queue_size=64,
                                      model="bench")
        decode = DisaggDecodeWorker(
            NativeEngine(model_cfg, EngineConfig(**kw), seed=0),
            plane.messaging, drouter, queue, worker_id="bench-sh",
            prefill_timeout_s=300.0, early_decode=False)
        prefill_worker = NativeEngineWorker(
            NativeEngine(model_cfg, EngineConfig(**kw), seed=0))
        txA = NicPaced(plane.kv, chunk_pages=2, window_chunks=2)
        prefill = PrefillWorker(prefill_worker, queue, txA,
                                plane.messaging)
        await decode.start()
        await prefill.start()
        rid_n = [0]

        def make_pre(tag, sampled):
            rid_n[0] += 1
            rid = f"sh-{tag}-{rid_n[0]}"
            salt = 137 * rid_n[0] + sum(tag.encode())
            skw = {}
            if sampled:
                skw = dict(sampling={"temperature": 0.8, "top_k": 40,
                                     "top_p": 0.95, "seed": 1234})
            return PreprocessedRequest(
                request_id=rid,
                token_ids=[(salt + 3 * j) % pmod + 1
                           for j in range(prompt_len)],
                stop=StopConditions(max_tokens=max_tokens,
                                    ignore_eos=True), **skw), rid

        async def one(tag, sampled=False, pre=None):
            if pre is None:
                pre, rid = make_pre(tag, sampled)
            else:
                rid = pre.request_id
            t0 = time.perf_counter()
            ttft = None
            toks = []
            async for frame in decode.generate(
                    pre.model_dump(exclude_none=True), Context(rid)):
                if ttft is None and frame.get("token_ids"):
                    ttft = time.perf_counter() - t0
                toks.extend(frame.get("token_ids", ()))
            return ttft, toks

        # size the per-NIC pacing off the real page payload: one
        # stream's wire time ~= wire_s regardless of tiny-vs-real model
        params = SamplingParams(max_tokens=1, temperature=0.0,
                                ignore_eos=True)
        prompt = [(11 * j) % pmod + 1 for j in range(prompt_len)]
        peng = prefill_worker.engine
        peng.add_request(EngineRequest("sz", prompt, params,
                                       prefill_only=True))
        while peng.has_work():
            peng.step()
        pages = peng.extract_pages(peng.scheduler.parked["sz"].pages)
        payload = pages["k"].nbytes + pages["v"].nbytes
        for leaf in ("k_scale", "v_scale"):
            if leaf in pages:
                payload += pages[leaf].nbytes
        NicPaced.nic_bytes_per_s = payload / wire_s
        await prefill_worker.submit(lambda eng: eng.release_parked("sz"))

        async def wall_leg(tag, tx, paced):
            """`transfers` sends of the extracted stack, p50 wall."""
            saved = NicPaced.nic_bytes_per_s
            if not paced:
                NicPaced.nic_bytes_per_s = float("inf")
            walls = []
            try:
                for r in range(transfers + 1):
                    rid = f"wall-{tag}-{paced}-{r}"
                    alloc = await decode.submit(
                        lambda eng, rid=rid: eng.allocate_remote(
                            EngineRequest(rid, prompt, params)))
                    t0 = time.perf_counter()
                    await tx.send_pages(
                        "bench-sh", rid, alloc.page_ids,
                        pages["k"], pages["v"],
                        k_scale=pages.get("k_scale"),
                        v_scale=pages.get("v_scale"),
                        alloc_epoch=alloc.alloc_epoch)
                    walls.append(time.perf_counter() - t0)
                    await decode.submit(
                        lambda eng, rid=rid: eng.release_remote(rid))
            finally:
                NicPaced.nic_bytes_per_s = saved
            walls = sorted(walls[1:])     # first send pays compiles
            return round(walls[len(walls) // 2] * 1e3, 2)

        async def ttft_leg(tag):
            await one(tag + "w")          # compile out of the timing
            vals = []
            for _ in range(requests):
                ttft, _ = await one(tag)
                vals.append(ttft)
            vals.sort()
            return round(vals[len(vals) // 2] * 1e3, 2)

        async def identity_probe(tag):
            """Token identity through the REMOTE path of this mode:
            fresh per-mode prompts run remote FIRST (no prefix to hit),
            then the same prompts re-run locally (router threshold
            lifted; the now-cached prefix is exact reuse) as the
            oracle. Greedy AND seeded-sampled must match."""
            ok = True
            for kind, sampled in (("g", False), ("s", True)):
                pre, _ = make_pre(f"id{kind}-{tag}", sampled)
                before = decode.remote_prefills
                _, remote_toks = await one(tag, pre=pre)
                if decode.remote_prefills == before:
                    raise RuntimeError(
                        f"identity probe id{kind}-{tag} never went "
                        "remote")
                saved = drouter.max_local_prefill_length
                drouter.max_local_prefill_length = 1 << 30
                oracle_pre = pre.model_copy(
                    update={"request_id": pre.request_id + "-o"})
                _, local_toks = await one(tag, pre=oracle_pre)
                drouter.max_local_prefill_length = saved
                ok = ok and (remote_toks == local_toks)
            return ok

        try:
            # aggregated TTFT reference (local prefill, threshold lifted)
            saved_thr = drouter.max_local_prefill_length
            drouter.max_local_prefill_length = 1 << 30
            ttft_agg = await ttft_leg("agg")
            drouter.max_local_prefill_length = saved_thr

            # mode A: single stream (legacy endpoint)
            server = await KvTransferServer(decode, "bench-sh").start()
            await server.register(plane.kv)
            ident_1 = await identity_probe("one")
            ttft_1 = await ttft_leg("one")
            wall_1 = await wall_leg("one", txA, paced=True)
            wall_1_raw = await wall_leg("one", txA, paced=False)
            await server.stop()
            await txA.close()
            await plane.kv.delete(transfer_key("bench-sh"))

            # mode B: N parallel (shard, host) streams
            group = await ShardedKvTransferGroup(
                decode, "bench-sh", hosts=n_streams,
                n_streams=n_streams).start()
            await group.register(plane.kv)
            txB = NicPaced(plane.kv, chunk_pages=2 * n_streams,
                           window_chunks=2)
            prefill.transfer = txB
            ident_n = await identity_probe("par")
            ttft_n = await ttft_leg("par")
            wall_n = await wall_leg("par", txB, paced=True)
            wall_n_raw = await wall_leg("par", txB, paced=False)
            identical = ident_1 and ident_n
            counters = {
                "remote_prefills": decode.remote_prefills,
                "parallel_streams": group.n_streams,
                "agg_ttft_ms": ttft_agg,
            }
            await txB.close()
            await group.stop()
        finally:
            await prefill.stop()
            await decode.stop()
        return (payload, wall_1, wall_n, wall_1_raw, wall_n_raw,
                ttft_1, ttft_n, identical, counters)

    (payload, wall_1, wall_n, wall_1_raw, wall_n_raw, ttft_1, ttft_n,
     identical, counters) = asyncio.run(main())
    if not identical:
        raise RuntimeError(
            "sharded transfer A/B output mismatch: greedy/seeded streams "
            "must be token-identical across 1-stream, N-stream, and the "
            "local oracle")
    result = {
        "prompt_len": prompt_len, "payload_bytes": payload,
        "n_streams": n_streams, "transfers": transfers,
        "wire_s_per_stream": wire_s,
        "wall_1_stream_ms": wall_1, "wall_n_stream_ms": wall_n,
        "paced_wall_ratio": round(wall_n / max(wall_1, 1e-9), 3),
        "wall_1_stream_raw_ms": wall_1_raw,
        "wall_n_stream_raw_ms": wall_n_raw,
        "raw_wall_ratio": round(wall_n_raw / max(wall_1_raw, 1e-9), 3),
        "disagg_ttft_1_stream_ms": ttft_1,
        "disagg_ttft_n_stream_ms": ttft_n,
        "disagg_ttft_ratio": round(ttft_n / max(ttft_1, 1e-9), 3),
        "token_identical": identical,
        **counters,
    }
    logf(f"sharded transfer A/B ({n_streams} streams, "
         f"{payload >> 20}MiB payload): paced wall {wall_1}ms -> "
         f"{wall_n}ms ({result['paced_wall_ratio']}x), raw "
         f"{wall_1_raw}ms -> {wall_n_raw}ms "
         f"({result['raw_wall_ratio']}x), disagg TTFT {ttft_1}ms -> "
         f"{ttft_n}ms ({result['disagg_ttft_ratio']}x), "
         f"token-identical {identical}")
    return result


def run_warm_prefix(model_cfg, base_kwargs=None, *, requests=4,
                    shared_pages=6, n_chips=1,
                    logf=None):
    """Cluster-pool warm-prefix TTFT ladder for extras["warm_prefix"]
    (ISSUE 13, ROADMAP item 2 — the millions-of-users shared-system-
    prompt scenario):

    1. cold        — a never-seen prefix prefills from scratch (the
                     denominator);
    2. local_hit   — the SAME engine re-serves the prefix (HBM prefix
                     cache, the pre-pool best case);
    3. pool_fetch  — the prefix was prefilled on engine A and published
                     into the SharedKvPool; engine B serves it by
                     fetching the pages at admission (cross-worker
                     reuse, no recompute);
    4. pool_prefetch — engine B additionally warmed the pages into HBM
                     during a simulated admission wait
                     (engine.prefetch_pool_pages, the PRESERVE window),
                     so the walk hits device memory;
    5. remote_fetch — the prefixes live in the served, replicated
                     ClusterKvPool (engine/pool_service.py: hash-ring
                     placement over 2 KvPoolHosts, R=2, checksum
                     re-verify on the serving host), and a fresh engine
                     serves by fetching through the replica walk — the
                     cross-HOST rung ISSUE 17 adds;
    6. remote_prefetch — same cluster pool, pages warmed through the
                     PRESERVE window before admission.

    Distinct shared prefixes per measured request keep each fetch
    genuinely cold on the serving engine; every TTFT sample is also
    observed into the llm_ttft_seconds histogram (SERVING.ttft).
    Greedy token identity pool-vs-cold is asserted inline — a pool
    serve that changed tokens would poison the measurement. CPU
    validation proves plumbing + ratio direction; the TPU ladder items
    (BENCH_SELF_r13_warm_prefix_tpu, BENCH_SELF_r17_pool_remote_tpu)
    give the hardware verdict."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.kv_pool import POOL_STATS, SharedKvPool
    from dynamo_tpu.engine.pool_service import (REMOTE_STATS, ClusterKvPool,
                                                KvPoolHost)
    from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
    from dynamo_tpu.observability.serving import SERVING

    logf = logf or log
    kw = dict(base_kwargs or PAGE_KWARGS)
    ps = kw["page_size"]
    pmod = min(1000, model_cfg.vocab_size - 2)
    # bound the prefix so (requests+1) distinct prefixes fit engine A's
    # page budget alongside a decode allocation
    shared_pages = max(2, min(shared_pages,
                              kw["num_pages"] // (2 * (requests + 1))))
    shared_len = shared_pages * ps
    params = SamplingParams(max_tokens=4, temperature=0.0, ignore_eos=True)

    def prefix(i):
        return [(7 * i + 3 * j) % pmod + 1 for j in range(shared_len)]

    def tail(i):
        return [(311 + 13 * i + 5 * j) % pmod + 1 for j in range(ps)]

    def ttft(eng, rid, prompt):
        t0 = _time.perf_counter()
        eng.add_request(EngineRequest(rid, prompt, params))
        toks = []
        while True:
            for ev in eng.step():
                if ev.request_id == rid and ev.token is not None:
                    if not toks:
                        dt = _time.perf_counter() - t0
                    toks.append(ev.token)
                if ev.request_id == rid and ev.finished:
                    SERVING.ttft.observe("bench-warm-prefix", "standard",
                                         value=dt)
                    return dt, toks
        # unreachable: max_tokens bounds the loop

    def build(pool=None, wid=""):
        eng = NativeEngine(model_cfg, EngineConfig(**kw), seed=0)
        if pool is not None:
            eng.attach_kv_pool(pool, wid)
        return eng

    def p50(vals):
        return round(sorted(vals)[len(vals) // 2] * 1e3, 2)

    pool = SharedKvPool(capacity_pages=kw["num_pages"] * 2)
    # engine A prefills every shared prefix and publishes it: the drain
    # tees sealed pages to the publish stream, which checksums at
    # capture; fetches below re-verify (engine/kv_pool.py)
    a = build(pool, "warm-a")
    for i in range(requests + 1):
        a.generate(prefix(i), params, f"seed-{i}")
        a.drain_kv_events()
    a._pool_stream.drain()
    seeded_entries = len(pool)

    cold = build()          # no pool: the from-scratch denominator
    b = build(pool, "warm-b")
    c = build(pool, "warm-c")
    # compile warmup on every engine (prefix 0 is the warm spare —
    # never measured), so XLA compiles sit outside every timing
    for eng, tag in ((cold, "w0"), (b, "w1"), (c, "w2")):
        ttft(eng, f"warm-{tag}", prefix(0) + tail(0))

    cold_v, local_v, fetch_v, pre_v = [], [], [], []
    cold_toks_by_i = {}
    identical = True
    for i in range(1, requests + 1):
        prompt = prefix(i) + tail(i)
        dt, cold_toks = ttft(cold, f"cold-{i}", prompt)
        cold_toks_by_i[i] = cold_toks
        cold_v.append(dt)
        dt, _ = ttft(cold, f"local-{i}", prompt)   # same engine: HBM hit
        local_v.append(dt)
        fetched_before = b.scheduler.pool_fetched_pages
        dt, pool_toks = ttft(b, f"fetch-{i}", prompt)
        fetch_v.append(dt)
        identical &= pool_toks == cold_toks
        assert b.scheduler.pool_fetched_pages > fetched_before, \
            "pool-fetch mode served without fetching (measurement void)"
        # PRESERVE window: warm BEFORE admission, then measure
        warmed = c.prefetch_pool_pages(prompt)
        assert warmed >= shared_pages - 1, \
            f"prefetch warmed {warmed} < {shared_pages - 1} pages"
        dt, _ = ttft(c, f"pre-{i}", prompt)
        pre_v.append(dt)
    for eng in (a, cold, b, c):
        eng.close()
    del a, cold, b, c

    # 5./6. REMOTE rungs: the pool as a served cluster component —
    # 2 KvPoolHosts behind a consistent-hash ring, R=2, every fetch
    # checksum-verified on the serving host before it crosses back
    # (ISSUE 17; failure model in docs/RESILIENCE.md). The facade is
    # interface-identical to SharedKvPool, so attach/publish/claim and
    # the PRESERVE prefetch path are the production code paths.
    cluster = ClusterKvPool(replicas=2)
    for hid in ("bench-ph0", "bench-ph1"):
        cluster.add_host(KvPoolHost(hid, capacity_pages=kw["num_pages"] * 2))
    cluster.run_rebalance()      # drain the (empty) join handoffs
    a2 = build(cluster, "warm-ra")
    for i in range(requests + 1):
        a2.generate(prefix(i), params, f"rseed-{i}")
        a2.drain_kv_events()
    a2._pool_stream.drain()
    d = build(cluster, "warm-rd")
    e = build(cluster, "warm-re")
    for eng, tag in ((d, "w3"), (e, "w4")):
        ttft(eng, f"warm-{tag}", prefix(0) + tail(0))
    remote_v, rpre_v = [], []
    for i in range(1, requests + 1):
        prompt = prefix(i) + tail(i)
        fetched_before = REMOTE_STATS.snapshot()["fetch_pages"]
        dt, rtoks = ttft(d, f"rfetch-{i}", prompt)
        remote_v.append(dt)
        identical &= rtoks == cold_toks_by_i[i]
        assert REMOTE_STATS.snapshot()["fetch_pages"] > fetched_before, \
            "remote-fetch mode served without a cluster fetch " \
            "(measurement void)"
        warmed = e.prefetch_pool_pages(prompt)
        assert warmed >= shared_pages - 1, \
            f"remote prefetch warmed {warmed} < {shared_pages - 1} pages"
        dt, _ = ttft(e, f"rpre-{i}", prompt)
        rpre_v.append(dt)
    for eng in (a2, d, e):
        eng.close()
    del a2, d, e

    result = {
        "shared_len": shared_len, "requests": requests,
        "pool_entries_seeded": seeded_entries,
        "cold_ttft_p50_ms": p50(cold_v),
        "local_hit_ttft_p50_ms": p50(local_v),
        "pool_fetch_ttft_p50_ms": p50(fetch_v),
        "pool_prefetch_ttft_p50_ms": p50(pre_v),
        "remote_fetch_ttft_p50_ms": p50(remote_v),
        "remote_prefetch_ttft_p50_ms": p50(rpre_v),
        "pool_fetch_cold_ttft_ratio":
            round(p50(fetch_v) / max(p50(cold_v), 1e-9), 3),
        "prefetch_fetch_ttft_ratio":
            round(p50(pre_v) / max(p50(fetch_v), 1e-9), 3),
        "remote_fetch_cold_ttft_ratio":
            round(p50(remote_v) / max(p50(cold_v), 1e-9), 3),
        "remote_prefetch_fetch_ttft_ratio":
            round(p50(rpre_v) / max(p50(remote_v), 1e-9), 3),
        "token_identity_greedy": identical,
        "pool_counters": {k: POOL_STATS.snapshot()[k] for k in (
            "publishes", "dedup_hits", "fetch_hits", "fetch_misses",
            "prefetch_pages", "quarantined")},
        "remote_counters": {k: REMOTE_STATS.snapshot()[k] for k in (
            "fetch_pages", "fetch_failovers", "fetch_exhausted",
            "publishes", "stale_epoch_rejected", "stale_epoch_landed")},
    }
    assert result["remote_counters"]["stale_epoch_landed"] == 0, \
        "stale-epoch write LANDED during bench (fence violated)"
    logf(f"warm-prefix TTFT p50: cold {result['cold_ttft_p50_ms']}ms, "
         f"local-hit {result['local_hit_ttft_p50_ms']}ms, pool-fetch "
         f"{result['pool_fetch_ttft_p50_ms']}ms "
         f"({result['pool_fetch_cold_ttft_ratio']}x cold), pool-prefetch "
         f"{result['pool_prefetch_ttft_p50_ms']}ms "
         f"({result['prefetch_fetch_ttft_ratio']}x fetch), remote-fetch "
         f"{result['remote_fetch_ttft_p50_ms']}ms "
         f"({result['remote_fetch_cold_ttft_ratio']}x cold), "
         f"remote-prefetch {result['remote_prefetch_ttft_p50_ms']}ms "
         f"({result['remote_prefetch_fetch_ttft_ratio']}x remote-fetch); "
         f"greedy identity {'OK' if identical else 'BROKEN'}")
    return result


def run_long_context(model_cfg, base_kwargs=None, *, budget_pages=6,
                     page_size=4, decode_tokens=16, n_chips=1,
                     logf=None):
    """Tiered-KV streaming decode ladder for extras["long_context"]
    (ISSUE 20, PERF.md §3h — the million-token-context lever):

    At each context rung (1x / 2x / 4x the streamed engine's HBM page
    budget) the SAME prompt decodes on two engines:

    - resident — an oversized-HBM oracle (every page stays in device
      memory; the pre-streaming best case and the ITL denominator);
    - streamed — an engine whose page budget is 1/4 of the top rung's
      context, cold pages spilled to the host tier and streamed back
      through the double-buffered window pool.

    Greedy token identity streamed-vs-resident is asserted inline at
    every rung — streaming that changed tokens would poison the
    measurement. Reported per rung: ITL p50/p95 for both engines plus
    the prefetch hit/late split (STREAM_STATS deltas); the headline is
    `itl_inflation_4x` = streamed/resident ITL p50 at the 4x rung —
    the price of attending beyond HBM, gated "lower" in BASELINE.json.
    CPU validation proves plumbing + ratio direction; the TPU ladder
    item (BENCH_SELF_r20_long_context_tpu) gives the hardware verdict."""
    import time as _time

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams
    from dynamo_tpu.engine.streaming import STREAM_STATS

    logf = logf or log
    ps = page_size
    pmod = min(1000, model_cfg.vocab_size - 2)
    top_pages = 4 * budget_pages
    mml = min(model_cfg.max_model_len, 2 * top_pages * ps)
    # decode_steps=1: one token per engine.step() on BOTH engines, so a
    # perf_counter stamp per step IS the inter-token latency (a decode
    # window would emit a burst of same-stamp tokens and fake ITL 0)
    common = dict(page_size=ps, max_slots=2, max_prefill_chunk=8 * ps,
                  prefill_buckets=(2 * ps, 4 * ps, 8 * ps),
                  max_model_len=mml, decode_steps=1)
    resident_eng = NativeEngine(
        model_cfg, EngineConfig(num_pages=2 * top_pages + 8, **common),
        seed=0)
    streamed_eng = NativeEngine(
        model_cfg, EngineConfig(num_pages=budget_pages,
                                host_pages=2 * top_pages + 8,
                                stream_pages=4,
                                stream_resident_pages=budget_pages - 2,
                                stream_hot_pages=2, **common),
        seed=0)
    params = SamplingParams(max_tokens=decode_tokens, temperature=0.0,
                            ignore_eos=True)

    def decode_itl(eng, rid, prompt):
        """(tokens, itl_ms list) — inter-token gaps after the first."""
        eng.add_request(EngineRequest(rid, prompt, params))
        toks, stamps = [], []
        while eng.has_work():
            for ev in eng.step():
                if ev.request_id == rid and ev.token is not None:
                    toks.append(ev.token)
                    stamps.append(_time.perf_counter())
        itl = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
        return toks, itl

    def pctl(xs, q):
        xs = sorted(xs)
        return round(xs[min(len(xs) - 1, int(q * len(xs)))], 3)

    # warmup: absorb prefill/decode compiles on both engines (the
    # warmup context fits residency, so the streamed engine's stream
    # programs still compile inside the 1x rung — its p50 is robust to
    # that one-off, and only rung p95s carry any residual compile)
    warm = [(11 * i + 5) % pmod + 1 for i in range(2 * ps)]
    decode_itl(resident_eng, "warm-res", warm)
    decode_itl(streamed_eng, "warm-str", warm)

    rungs = {}
    identical = True
    for m in (1, 2, 4):
        prompt_len = m * budget_pages * ps - decode_tokens
        prompt = [(7 * i + 3) % pmod + 1 for i in range(prompt_len)]
        r_toks, r_itl = decode_itl(resident_eng, f"res-{m}x", prompt)
        s0 = STREAM_STATS.snapshot()
        s_toks, s_itl = decode_itl(streamed_eng, f"str-{m}x", prompt)
        s1 = STREAM_STATS.snapshot()
        identical = identical and (s_toks == r_toks)
        hits = int(s1["prefetch_hit"] - s0["prefetch_hit"])
        lates = int(s1["prefetch_late"] - s0["prefetch_late"])
        rungs[f"{m}x"] = {
            "context_tokens": prompt_len + decode_tokens,
            "context_pages": m * budget_pages,
            "streamed": bool(s1["stream_seqs"] - s0["stream_seqs"]),
            "resident_itl_p50_ms": pctl(r_itl, 0.50),
            "resident_itl_p95_ms": pctl(r_itl, 0.95),
            "streamed_itl_p50_ms": pctl(s_itl, 0.50),
            "streamed_itl_p95_ms": pctl(s_itl, 0.95),
            "prefetch_hit": hits, "prefetch_late": lates,
            "pages_spilled": int(s1["pages_spilled"]
                                 - s0["pages_spilled"]),
        }
        logf(f"long-context {m}x ({prompt_len + decode_tokens} tok, "
             f"streamed={rungs[f'{m}x']['streamed']}): resident ITL p50 "
             f"{rungs[f'{m}x']['resident_itl_p50_ms']}ms, streamed "
             f"{rungs[f'{m}x']['streamed_itl_p50_ms']}ms, "
             f"hit/late {hits}/{lates}; identity "
             f"{'OK' if s_toks == r_toks else 'BROKEN'}")
    assert identical, \
        "streamed decode diverged from the resident oracle (gate broken)"
    assert rungs["4x"]["streamed"] and rungs["4x"]["pages_spilled"] > 0, \
        "the 4x rung never actually streamed — the ladder measured nothing"
    top = rungs["4x"]
    hits, lates = top["prefetch_hit"], top["prefetch_late"]
    result = {
        "page_size": ps, "budget_pages": budget_pages,
        "decode_tokens": decode_tokens, "rungs": rungs,
        "itl_inflation_4x": round(
            top["streamed_itl_p50_ms"]
            / max(top["resident_itl_p50_ms"], 1e-9), 4),
        "prefetch_hit_ratio_4x": round(hits / max(hits + lates, 1), 4),
        "token_identity_ok": identical,
    }
    assert hits > lates, \
        f"prefetch hits ({hits}) must dominate lates ({lates})"
    logf(f"long-context headline: ITL inflation at 4x budget "
         f"{result['itl_inflation_4x']}x, prefetch hit ratio "
         f"{result['prefetch_hit_ratio_4x']}")
    return result


def run_parity(model_cfg, engine_box=None, logf=None):
    """Window-vs-single-step greedy token parity on the current backend.

    ONE implementation shared by the bench parity phase and the standalone
    window-runner (tools/tpu_parity_quick.py), so both always validate the
    same configuration. The window side is the split-KV pregather +
    deferred-writeback + adaptive-ladder engine (decode_steps=64) on a
    fresh prompt; 96 tokens crosses a page boundary and exercises multiple
    ladder rungs (64 + smaller tails). The single-step twin is built with
    the same seed => identical params.

    engine_box: a single-element list holding an already-built window
    engine to reuse (the bench's measurement engine) — the list is emptied
    here so the engine can be freed before the twin is built (HBM). None
    builds a fresh decode_steps=64 engine. Returns the verdict string
    ("exact(N tokens)" / "DIVERGED@i").
    """
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import SamplingParams

    logf = logf or log
    # modulus clamped inside the model vocab: BENCH_MODEL=tiny (vocab 256)
    # validation runs would otherwise feed OOV ids the engine now rejects
    pmod = min(1000, model_cfg.vocab_size - 2)
    prompt = [(31 * j) % pmod + 1 for j in range(64)]
    params = SamplingParams(max_tokens=96, temperature=0.0, ignore_eos=True)

    if engine_box:
        # reuse path: validates the measurement engine AS BUILT (whatever
        # decode_steps the bench ran with)
        engine = engine_box.pop()
        # drain perf-phase state so no prefix/cache reuse leaks in
        for rid in list(engine.scheduler.params):
            engine.abort(rid)
        while engine.has_work():
            engine.step()
    else:
        engine = NativeEngine(
            model_cfg, EngineConfig(decode_steps=64, **PAGE_KWARGS), seed=0)
    got = engine.generate(prompt, params, "parity-window")
    del engine  # free HBM before building the single-step twin
    e1 = NativeEngine(
        model_cfg, EngineConfig(decode_steps=1, **PAGE_KWARGS), seed=0)
    ref = e1.generate(prompt, params, "parity-single")
    if got == ref:
        logf(f"parity OK: {len(ref)} greedy tokens identical")
        return f"exact({len(ref)} tokens)"
    div = next((i for i, (a, b) in enumerate(zip(got, ref))
                if a != b), min(len(got), len(ref)))
    logf(f"parity FAILURE at token {div}: window={got[:div + 3]} "
         f"single={ref[:div + 3]}")
    # attribution (r5 capture diverged@39 on TPU): the window and
    # single-step paths are different-but-equivalent programs, so on bf16
    # an argmax whose top-2 logit gap sits below the accumulation epsilon
    # can flip without any path being wrong. Re-run the single-step twin
    # with logprobs and report the gap at the divergence token: a tiny
    # margin with the window's token as the runner-up is a benign
    # near-tie; a large margin or a token outside the top-2 is a real bug.
    del e1
    margin = runner_up = None
    try:
        margin, runner_up = _parity_margin(model_cfg, prompt, params, div,
                                           ref, logf)
    except Exception as e:  # the probe is diagnostics, never fatal
        logf("margin probe failed:", e)
    if margin is not None:
        near = runner_up == got[div] and margin < 0.02
        logf(f"divergence margin: top-2 logprob gap {margin:.3e} at token "
             f"{div}; window took "
             f"{'the runner-up' if runner_up == got[div] else 'a NON-top-2 token'}")
        if near:
            return (f"DIVERGED@{div}(near-tie: margin {margin:.2e}, "
                    f"window took runner-up)")
        return (f"DIVERGED@{div}(margin {margin:.2e}, "
                f"runner_up={runner_up})")
    return f"DIVERGED@{div}"


def _parity_margin(model_cfg, prompt, params, div, ref, logf):
    """Top-2 logprob gap at generated-token index ``div`` on the
    single-step path, and the runner-up token id.

    The probe compiles the with-logprobs decode variant — a THIRD
    distinct program — so on bf16 it could itself flip a near-tie before
    ``div`` and report a margin for the wrong token history. The replay
    is therefore checked token-for-token against the single-step
    reference up to ``div`` and the margin discarded on mismatch
    (code-review r5)."""
    import dataclasses

    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import EngineRequest

    e = NativeEngine(
        model_cfg, EngineConfig(decode_steps=1, **PAGE_KWARGS), seed=0)
    p2 = dataclasses.replace(params, logprobs=2)
    e.add_request(EngineRequest("margin-probe", prompt, p2))
    toks, tops = [], []
    while len(tops) <= div and e.has_work():
        for ev in e.step():
            if ev.token is not None and ev.top_logprobs:
                toks.append(ev.token)
                tops.append(ev.top_logprobs)
    if toks[:div] != list(ref[:div]):
        logf("margin probe replay diverged from the single-step reference "
             "before the divergence token; margin unattributable")
        return None, None
    top = tops[div]
    if len(top) < 2:
        return None, None
    return top[0][1] - top[1][1], top[1][0]


def run_phases(st: Run) -> None:
    log("phase: importing jax, initializing the backend")
    import jax

    from dynamo_tpu.utils.launch import enable_compile_cache
    enable_compile_cache()
    st.set_phase("backend_init")
    devices = jax.devices()
    n_chips = len(devices)
    platform = devices[0].platform
    st.result["device"] = {"platform": platform,
                           "kind": devices[0].device_kind,
                           "count": n_chips}
    log(f"backend up: {devices} ({platform})")
    model_name = os.environ.get("BENCH_MODEL", "llama3-1b")
    if platform != "tpu":
        if model_name == "llama3-1b":
            raise SystemExit(
                f"bench.py measures the llama3-1b flagship on a TPU; the "
                f"backend here is {platform!r}. BENCH_MODEL=tiny validates "
                f"the phases on the CPU.")
        # a CPU validation run: not a device metric, and the nominal
        # baseline (a v5e roofline figure) does not apply to it
        st.result["unit"] = f"tokens/s ({platform} validation)"
        st.result["vs_baseline"] = None

    import jax.numpy as jnp

    from dynamo_tpu.engine.config import EngineConfig, get_model_config
    from dynamo_tpu.engine.engine import NativeEngine
    from dynamo_tpu.engine.scheduler import EngineRequest, SamplingParams

    st.set_phase("kernel_probe")
    # the engine's serving default is the deferred-write GATHER decode
    # (models/llama._decode_kernel_mode); the probe proves the Pallas
    # kernel still compiles for the flagship's packed hd=64 geometry. On
    # a TPU it passes or the run fails; elsewhere there is nothing to
    # compile it for, and the result line says it was not probed.
    kernel = f"not probed (backend {platform})"
    if platform == "tpu":
        log("phase: probing pallas decode kernel with a tiny call")
        from dynamo_tpu.ops.paged_attention import decode_paged_attention
        # the flagship's exact head geometry (h=32, hkv=8 -> G=4, hd=64,
        # ps=64): probes the packed-DMA path
        q = jnp.ones((1, 32, 64), jnp.bfloat16)
        k = jnp.ones((8, 2, 64, 64), jnp.bfloat16)
        pt = jnp.zeros((1, 1), jnp.int32)
        lens = jnp.ones((1,), jnp.int32)
        jax.block_until_ready(decode_paged_attention(q, k, k, pt, lens))
        kernel = "compiles"
        log("kernel probe OK")

    # BENCH_MODEL=tiny lets CI validate every phase on CPU in seconds;
    # the real bench always runs the llama3-1b flagship. (The metric name
    # was already derived from these env vars in Run.__init__.)
    model_cfg = get_model_config(model_name)  # decode_kernel="auto" = gather
    # BENCH_QUANT=int8: weight-only int8 serving (ops/quant.py) — the
    # decode path is weight-read-bound, so this measures the HBM-BW lever
    quant = os.environ.get("BENCH_QUANT", "")
    if quant:  # value already validated by metric_name() at init
        import dataclasses
        model_cfg = dataclasses.replace(model_cfg, quant=quant)
        st.result["extras"]["quant"] = quant
    slots = PAGE_KWARGS["max_slots"]  # engine geometry drives the workload
    # 64-step windows: the window-pregathered decode amortizes its per-
    # window gather/writeback + host dispatch over more tokens (997 tok/s
    # at 32 -> 1215 at 64 on v5e-1). Bigger windows keep helping in
    # isolation (1374 at 128) but need a larger max_tokens budget, which
    # crosses the page-table bucket from 16 to 32 pages and doubles the
    # attention read — 64 is the knee at this workload's bucket. The
    # scheduler's adaptive clamp keeps short-remainder requests on smaller
    # compiled variants either way.
    decode_steps = int(os.environ.get("BENCH_DECODE_STEPS", "64"))
    # prompt-id modulus clamped inside the vocab (tiny validation runs)
    pmod = min(1000, model_cfg.vocab_size - 2)
    cfg = EngineConfig(decode_steps=decode_steps, **PAGE_KWARGS)
    st.result["extras"].update(kernel=kernel, decode_steps=decode_steps,
                               slots=slots)

    # max_tokens covers warmup (2 windows) + 6 timed chunks (>=1 window
    # each) so no slot runs dry mid-measurement (empty slots would deflate
    # tok/s; an exhausted budget would also shrink the adaptive window)
    prompt_len = 128
    budget_tokens = (2 + 6 * max(1, 80 // decode_steps) + 2) * decode_steps
    # clamp to the context: oversized BENCH_DECODE_STEPS must degrade to
    # shorter measurements, not a ValueError at admission
    max_toks = min(max(560, budget_tokens), cfg.max_model_len - prompt_len)
    params = SamplingParams(max_tokens=max_toks, temperature=0.0,
                            ignore_eos=True)

    st.set_phase("engine_build")
    log("phase: building engine (init_params + init_cache compiles)")
    engine = NativeEngine(model_cfg, cfg, seed=0)

    def add_all(tag):
        # prompts are distinct across tags so the TTFT phase can't ride the
        # prefix cache built by warmup (that would fake a near-zero TTFT)
        salt = sum(tag.encode()) * 131
        for i in range(slots):
            prompt = [(salt + 7 * i + j) % pmod + 1
                      for j in range(prompt_len)]
            engine.add_request(EngineRequest(f"{tag}-{i}", prompt, params))

    st.set_phase("warmup")
    log(f"phase: warmup — batched prefill of all {slots} slots + 2 decode "
        f"windows of {decode_steps}")
    add_all("warm")
    n_pf = 0
    while engine.scheduler.waiting:
        engine.step()
        n_pf += 1
    log(f"prefill done ({n_pf} steps)")
    for _ in range(2):
        engine.step()
    log("warmup done; decode window compiled")

    st.set_phase("decode_chunks")
    log("phase: timed decode chunks (adaptive; records best chunk)")
    chunk_windows = max(1, 80 // decode_steps)
    max_chunks = 6
    best = 0.0
    for c in range(max_chunks):
        t0 = time.perf_counter()
        tokens = 0
        for _ in range(chunk_windows):
            tokens += sum(1 for ev in engine.step() if ev.token is not None)
        dt = time.perf_counter() - t0
        tok_s = tokens / dt
        best = max(best, tok_s)
        st.record(best, n_chips)
        log(f"chunk {c}: {tok_s:.1f} tok/s ({tokens} tokens / {dt:.3f}s); "
            f"best {best:.1f}")
    # decode pipeline occupancy for this capture (docs/PERF.md): how many
    # windows committed while a follow-up executed on device, how many
    # reconciliation fallbacks, and whether steady-state windows really
    # stayed plan-upload-free — the attribution companion to the tok/s
    # number (the phase split of every call is the StepLedger's record:
    # engine.ledger.calls(), `llm_engine_host_*_seconds` on /metrics)
    st.result["extras"]["decode_pipeline"] = {
        "depth": engine.cfg.pipeline_depth,
        "windows": engine.decode_windows,
        "pipelined": engine.pipeline_windows,
        "overlapped": engine.pipeline_overlapped,
        "fallbacks": engine.pipeline_fallbacks,
        "host_syncs": engine.decode_host_syncs,
        "plan_uploads": engine.decode_plan_uploads,
    }

    st.set_phase("ttft")
    log("phase: TTFT — drain, then 8 fresh concurrent prompts "
        "(batched prefill; north-star denominator, BASELINE.md)")
    # drain current requests so the TTFT engine starts idle
    for rid in list(engine.scheduler.params):
        engine.abort(rid)
    while engine.has_work():
        engine.step()
    t_add = time.perf_counter()
    add_all("ttft")
    first_token_at = {}
    while engine.has_work() and len(first_token_at) < slots:
        for ev in engine.step():
            if ev.token is not None and ev.request_id not in first_token_at:
                first_token_at[ev.request_id] = time.perf_counter() - t_add
    if first_token_at:
        ttfts = sorted(first_token_at.values())
        p50 = ttfts[len(ttfts) // 2]
        # all prompts prefill in one batched step: prefill throughput is
        # total prompt tokens over the time to the LAST first-token
        prefill_tok_s = slots * prompt_len / max(ttfts[-1], 1e-9)
        st.result["extras"].update(
            ttft_p50_ms=round(p50 * 1000, 1),
            ttft_p99_ms=round(ttfts[-1] * 1000, 1),
            prefill_tok_s=round(prefill_tok_s, 1))
        log(f"TTFT p50 {p50 * 1000:.1f} ms, max {ttfts[-1] * 1000:.1f} ms; "
            f"prefill {prefill_tok_s:.0f} tok/s")

    st.set_phase("churn")
    log("phase: agg-under-churn vs pure decode (the disagg ratio's "
        "one-chip denominator/numerator, BASELINE.md north star)")
    # Aggregated serving under continuous arrivals: every finished request
    # is replaced by a fresh prompt, so prefill chunks steal device steps
    # from decode — exactly the interference disaggregation removes (the
    # reference's 1-node +30% claim, docs/architecture.md:57-61). The
    # pure-decode number from the chunk phase (all slots busy, no arrivals)
    # is what a dedicated decode engine achieves; the ratio is the measured
    # one-chip upper bound for disagg gain at this workload shape. Prompts
    # are 8x the decode length (512:64) to approximate the reference's
    # long-ISL/short-OSL benchmark shape (3K ISL / 150 OSL).
    churn_isl = 4 * prompt_len  # 512
    next_id = 0

    def add_fresh():
        # per-request decode budgets staggered around 64 (mean preserved:
        # the 512:64 long-ISL/short-OSL shape stands): uniform budgets
        # made every slot finish at the SAME window, so replacement
        # prefills ran against an idle decode set and the phase measured
        # zero interference — the exact effect it exists to measure.
        # Staggering desynchronizes finishes, so each arrival's prefill
        # lands while the other slots are mid-decode (real churn).
        nonlocal next_id
        salt = 977 * (next_id + 1)
        engine.add_request(EngineRequest(
            f"churn-{next_id}",
            [(salt + 3 * j) % pmod + 1 for j in range(churn_isl)],
            SamplingParams(max_tokens=48 + (next_id % 5) * 8,
                           temperature=0.0, ignore_eos=True)))
        next_id += 1

    def pctile(sorted_xs, q):
        return sorted_xs[min(len(sorted_xs) - 1,
                             int(q * (len(sorted_xs) - 1) + 0.5))]

    def churn_pass(tag, budget):
        """One agg-under-churn measurement at the given mixed budget.

        Beyond tok/s, records what the fused-step scheduler changes:
        inter-token latency p50/p95/p99 (per-request gaps between
        consecutive token ARRIVALS at the commit boundary — window
        bursts land together, so the upper percentiles see the stall a
        prefill step injects) and decode_stall_steps (device steps where
        running streams emitted nothing). The pair makes the mixed-step
        gain attributable, not just a tok/s delta."""
        engine.scheduler.mixed_token_budget = budget
        for rid in list(engine.scheduler.params):
            engine.abort(rid)
        while engine.has_work():
            engine.step()
        for _ in range(slots):
            add_fresh()
        # warm this scheduler mode's mix until a full replacement cycle
        # completed (every slot finished + refilled at least once):
        # staggered budgets touch several (rows, chunk-bucket, window
        # rung) combos, and any compile landing inside the timed loop
        # would masquerade as a multi-second ITL outlier
        warm_finishes = 0
        for _ in range(600):
            for ev in engine.step():
                if ev.finished:
                    add_fresh()
                    warm_finishes += 1
            if warm_finishes >= slots:
                break
        stall0 = engine.decode_stall_steps
        sync0 = engine.decode_host_syncs
        mixed0 = engine.mixed_steps
        last_at = {}
        itl = []
        t0 = time.perf_counter()
        tokens = 0
        deadline = t0 + 15.0
        while time.perf_counter() < deadline:
            events = engine.step()
            now = time.perf_counter()
            for ev in events:
                if ev.token is not None:
                    tokens += 1
                    prev = last_at.get(ev.request_id)
                    if prev is not None:
                        itl.append(now - prev)
                    last_at[ev.request_id] = now
                if ev.finished:
                    last_at.pop(ev.request_id, None)
                    add_fresh()
        dt = time.perf_counter() - t0
        tok_s = tokens / dt / max(1, n_chips)
        itl.sort()
        rec = {
            "tok_s": round(tok_s, 1),
            "decode_stall_steps": engine.decode_stall_steps - stall0,
            "mixed_steps": engine.mixed_steps - mixed0,
            "host_syncs": engine.decode_host_syncs - sync0,
        }
        if itl:
            rec.update(
                itl_p50_ms=round(pctile(itl, 0.50) * 1000, 2),
                itl_p95_ms=round(pctile(itl, 0.95) * 1000, 2),
                itl_p99_ms=round(pctile(itl, 0.99) * 1000, 2))
        log(f"churn[{tag}] {tok_s:.1f} tok/s/chip, stalls "
            f"{rec['decode_stall_steps']}, itl p99 "
            f"{rec.get('itl_p99_ms')}ms")
        return rec

    # mixed (the default scheduler) first, then the alternating baseline
    # IN THE SAME RUN (same engine, same workload — the budget knob is
    # runtime-flippable, so the A/B shares every compiled program that
    # both modes use and the delta is attributable to the scheduler).
    # NOTE (docs/PERF.md §3b): on CPU validation runs the mixed tok/s is
    # EXPECTED to come out worse — compute-bound hosts pay the fused
    # step's row padding serially; the CPU evidence is the stall/sync
    # counters, the tok/s + ITL verdict is the TPU capture
    mixed_budget = engine.cfg.mixed_token_budget
    churn_mixed = churn_pass("mixed", mixed_budget)
    churn_alt = churn_pass("alternating", 0)
    engine.scheduler.mixed_token_budget = mixed_budget
    agg_tok_s = churn_mixed["tok_s"]
    pure = st.result["value"]
    st.result["extras"].update(
        agg_churn_tok_s=agg_tok_s,
        churn_mixed=churn_mixed,
        churn_alternating=churn_alt,
        disagg_decode_gain=round(pure / agg_tok_s, 3) if agg_tok_s else None)
    log(f"agg-under-churn {agg_tok_s:.1f} tok/s/chip (alternating "
        f"{churn_alt['tok_s']:.1f}) vs pure decode {pure:.1f}; "
        f"decode-side disagg gain bound "
        f"{pure / max(agg_tok_s, 1e-9):.2f}x")

    # optional evidence phases: each runs unless its BENCH_* switch is "0"
    # or too little of BUDGET_S is left; a failure is recorded in the
    # result line and in the exit code (Run.evidence), and the rest run
    def left(s):
        return time.time() - T0 < BUDGET_S - s

    if os.environ.get("BENCH_OVERLAP", "1") != "0" and left(180):
        st.set_phase("transfer_overlap")
        log("phase: disagg TTFT A/B — wait-for-final-chunk vs early-decode"
            " overlap, + router prefix-only vs transfer-aware (ISSUE 11)")
        st.evidence("transfer_overlap", "transfer overlap A/B",
                    lambda: run_transfer_overlap_ab(
                        model_cfg, PAGE_KWARGS, n_chips=n_chips, logf=log))

    if os.environ.get("BENCH_SHARDED", "1") != "0" and left(180):
        st.set_phase("sharded_transfer")
        log("phase: sharded transfer A/B — 1-stream vs N-(shard, host)-"
            "stream KV transfer wall time + disagg TTFT (ISSUE 15)")
        st.evidence("sharded_transfer", "sharded transfer A/B",
                    lambda: run_sharded_transfer_ab(
                        model_cfg, PAGE_KWARGS, n_chips=n_chips, logf=log))

    if os.environ.get("BENCH_WARM_PREFIX", "1") != "0" and left(120):
        st.set_phase("warm_prefix")
        log("phase: warm-prefix TTFT ladder — cold vs local-hit vs "
            "pool-fetch vs pool-prefetch over the shared KV pool "
            "(ISSUE 13)")
        st.evidence("warm_prefix", "warm-prefix ladder",
                    lambda: run_warm_prefix(
                        model_cfg, PAGE_KWARGS, n_chips=n_chips, logf=log))

    if os.environ.get("BENCH_KVQ", "1") != "0" and left(180):
        st.set_phase("kv_quant_ab")
        log("phase: kv_quant A/B — capacity at fixed HBM page budget + "
            "int8-KV churn pass (ROADMAP item 5 evidence)")
        st.evidence("kv_quant", "kv_quant A/B",
                    lambda: run_kv_quant_ab(
                        model_cfg, PAGE_KWARGS, seconds=10.0,
                        n_chips=n_chips, logf=log))

    if os.environ.get("BENCH_DECODE_KERNEL", "1") != "0" and left(120):
        st.set_phase("decode_kernel_ab")
        log("phase: decode kernel A/B — frozen legacy vs unified ragged "
            "kernel, token-identity enforced (ISSUE 18)")
        st.evidence("decode_kernel", "decode kernel A/B",
                    lambda: run_decode_kernel_ab(
                        model_cfg, PAGE_KWARGS, n_chips=n_chips, logf=log))

    if os.environ.get("BENCH_LONG_CONTEXT", "1") != "0" and left(120):
        st.set_phase("long_context")
        log("phase: long-context streaming ladder — resident vs streamed "
            "ITL at 1x/2x/4x the HBM page budget, token identity + "
            "prefetch hit/late split (ISSUE 20)")
        st.evidence("long_context", "long-context ladder",
                    lambda: run_long_context(
                        model_cfg, PAGE_KWARGS, n_chips=n_chips, logf=log))

    if os.environ.get("BENCH_SPEC") == "oracle":
        st.set_phase("spec_ceiling")
        log("phase: speculative-decoding ceiling — plain greedy pass "
            "records the oracle continuation, then a spec engine re-runs "
            "the same prompts with the oracle as its draft source "
            "(acceptance ~1.0): the verify path's full-acceptance "
            "throughput vs the window path on the identical workload")
        spec_k = int(os.environ.get("BENCH_SPEC_K", "8"))
        for rid in list(engine.scheduler.params):
            engine.abort(rid)
        while engine.has_work():
            engine.step()
        sp_params = SamplingParams(max_tokens=128, temperature=0.0,
                                   ignore_eos=True)
        sp_prompts = [[(311 + 7 * i + 3 * j) % pmod + 1
                       for j in range(prompt_len)] for i in range(slots)]

        def timed_pass(eng, tag):
            outs = {i: [] for i in range(slots)}

            def collect(events):
                c = 0
                for ev in events:
                    if ev.token is not None:
                        c += 1
                        outs[int(ev.request_id.rsplit("-", 1)[1])].append(
                            ev.token)
                return c

            for i, p in enumerate(sp_prompts):
                eng.add_request(EngineRequest(f"{tag}-{i}", p, sp_params))
            # the prefill drain sits outside the timing but its events
            # carry each request's FIRST token (and any decode windows the
            # prefill-streak limit interleaves) — dropping them shifted the
            # oracle by one and zeroed acceptance (code-review r5)
            while eng.scheduler.waiting:
                collect(eng.step())
            t0 = time.perf_counter()
            n = 0
            while eng.has_work():
                n += collect(eng.step())
            return outs, n / (time.perf_counter() - t0)

        plain_outs, plain_tok_s = timed_pass(engine, "spec-plain")
        log(f"plain pass: {plain_tok_s:.1f} tok/s")
        oracle = {tuple(p): list(p) + plain_outs[i]
                  for i, p in enumerate(sp_prompts)}

        def oracle_propose(tokens, k, min_ngram=2, max_ngram=4,
                           max_scan=4096):
            vocab = model_cfg.vocab_size
            for p, full in oracle.items():
                lp = len(p)
                if len(tokens) >= lp and tuple(tokens[:lp]) == p:
                    out = full[len(tokens):len(tokens) + k]
                    # truncate at the first id outside the vocab: the
                    # recorded history feeds the verify forward's
                    # embedding take verbatim (dynalint R1)
                    for j, t in enumerate(out):
                        if not 0 <= t < vocab:
                            return out[:j]
                    return out
            return []

        del engine  # free HBM before the spec twin (same seed => params)
        from dynamo_tpu.engine import spec as spec_mod
        real_propose = spec_mod.ngram_propose
        spec_mod.ngram_propose = oracle_propose
        try:
            import dataclasses as _dc
            spec_engine = NativeEngine(
                model_cfg, _dc.replace(cfg, spec_decode="ngram",
                                       spec_k=spec_k), seed=0)
            spec_outs, spec_tok_s = timed_pass(spec_engine, "spec-run")
            acc = (spec_engine.spec_accepted_tokens
                   / max(1, spec_engine.spec_proposed_tokens))
        finally:
            spec_mod.ngram_propose = real_propose
        exact = spec_outs == plain_outs
        st.result["extras"].update(
            spec_ceiling_tok_s=round(spec_tok_s, 1),
            spec_plain_tok_s=round(plain_tok_s, 1),
            spec_k=spec_k, spec_acceptance=round(acc, 3),
            spec_exact=exact,
            spec_speedup=round(spec_tok_s / max(plain_tok_s, 1e-9), 3))
        verdict_txt = ("identical" if exact else
                       "DIVERGED (bf16 near-ties on tpu or a bug on cpu)")
        log(f"spec ceiling: {spec_tok_s:.1f} tok/s vs plain "
            f"{plain_tok_s:.1f} ({spec_tok_s / max(plain_tok_s, 1e-9):.2f}x"
            f"), acceptance {acc:.3f}, outputs {verdict_txt}")
        # the measurement engine was freed for the spec twin; the parity
        # comparison belongs to the standard (non-spec) capture
        st.result["extras"]["parity"] = "skipped (BENCH_SPEC run)"
        st.set_phase("done")
        return

    st.set_phase("parity")
    log("phase: numerical parity — 64-step split-KV window vs the "
        "single-step decode path, token-for-token greedy (CPU tests can't "
        "see Mosaic/XLA-TPU divergence)")
    if time.time() - T0 > BUDGET_S - 120:
        log("approaching deadline; skipping parity phase")
        st.result["extras"]["parity"] = "skipped"
        st.set_phase("done")
        return
    box = [engine]
    del engine  # run_parity must hold the only reference to free HBM
    st.result["extras"]["parity"] = run_parity(model_cfg, engine_box=box,
                                               logf=log)
    st.set_phase("done")


def main() -> int:
    st = Run()
    try:
        run_phases(st)
    except Exception as e:
        log(f"phase {st.phase} FAILED {type(e).__name__}: {e}")
        traceback.print_exc(file=sys.stderr)
        st.result["extras"].setdefault("failure",
                                       f"{st.phase}: {type(e).__name__}: {e}")
        st.failed.append(st.phase)
    if st.failed:
        st.result["extras"]["failed_phases"] = st.failed
    print(json.dumps(st.result), flush=True)
    log("final:", st.result)
    append_trajectory(
        st.result, (st.result.get("device") or {}).get("platform", ""))
    return 1 if st.failed or st.result["value"] <= 0 else 0


if __name__ == "__main__":
    sys.exit(main())
