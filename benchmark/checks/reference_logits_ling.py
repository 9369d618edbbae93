"""Set-up check: Ling-3.0-flash-VL's served path against its plain
reference, on the chip, at the published widths and under the cell's own
sizes, on log-probabilities and not on sampled tokens.

Three seeded prompts of 40, 200 and 700 tokens (one page; the cell's own
prompts; a context past every request of the cell, eleven pages) are sent
greedy through the socket with `logprobs` and the most `top_logprobs` the
frontend gives (8), 16 tokens each, one after the other and each BESIDE
NINE ROWS THAT ARE DECODING (`HOLDERS`: greedy requests of 40 tokens that
outlast the three), so that every step that carries a compared token is a
step of ten rows, as the timed window's are steps of many: the first token
comes from prefill through `[16, 64]` and `[16, 256]` mixed steps (the
linear layers' chunkwise form for the chunk row and their one-token form
for the nine decode rows in ONE step over state slots, the split that
`models/llama.kda_mix` makes only past eight rows; latent attention in the
absorbed form over the one-leaf cache, the share's experts through the
sorted dispatch), the rest from decode through the state slots, the cache
and ten-row decode windows. The reference
(`benchmark/reference/ling.py`: float32, `highest` matmul precision, the
per-token recurrence, attention in the EXPANDED form, every held expert on
every token) then runs one full forward pass a prompt over prompt +
generated tokens from the engine's own weight arrays, a block of experts at
a time, and applies the head and the log-softmax at the 16 compared
positions only. Every served value at the served ids is compared: 3 x 16 x
(8 + 1) = 432 numbers.

Three readings of the 432 |differences|: the median and the 90th
percentile, which are held to limits, and the largest, which is reported
beside them and held to none (a maximum over flipped experts, as in
checks/reference_logits_moonlight.py: see LIMITS below). A failure makes
the run not `correct`.

Only where the configuration's `meta.json` has a `reference_check` key
whose module is "ling". `checks/reference_logits.py` is loaded by path for
what the checks share (`served_rows`, `token_id`), with its prompt lengths
replaced on this private copy of the module.
"""
from __future__ import annotations

import asyncio
import importlib.util
import json
import math
import os
import random
import statistics
import sys
import time

PROMPTS = (40, 200, 700)
SEED = None     # None: the shared check's own draw of the prompts
# rows that decode beside every compared prompt: with the prompt's own row
# a step of ten, past the eight at which kda_mix splits a step's rows into
# one-token rows and chunk rows. (prompt tokens, max_tokens): 384 tokens
# outlast the three compared requests' ~60 steps several times over
HOLDERS = 9
HOLDER_TOKENS = (40, 384)

# (90th percentile, median) of |served - reference| over the 432
# log-probabilities. The weights are the same bfloat16 values on both
# sides; the served path rounds every activation, the stored latent rows,
# the convolution's tail and each projection's output to bfloat16 and
# keeps the linear layers' state and its arithmetic in float32. As for
# Moonlight, what decides the LARGEST reading is the router: 512 sigmoid
# scores of a seeded random gate lie close together, a bfloat16-sized
# change of the input flips the 8th and 9th expert (or the 4th and 5th
# group) of a token in a fair share of (token, layer) pairs, and a flipped
# expert weighs a renormalised ~1/8 x 2.5 of one expert's output where it
# is held here. So `largest` is printed and limited by nothing, and the
# cell is held on the median and the 90th percentile.
# The limits rest on the ONE draw this check runs. Its prompts come from
# the shared check's fixed SEED (4242), its weights from the engine's fixed
# seed, its requests are greedy and sent in a fixed order beside rows that
# outlast them: a run reads what the run before it read (the rehearsal
# reads the same digits twice), and `--seed` moves nothing here. Another
# draw of the prompts reads up to 0.211 / 0.0616 sound ("alone" below) and
# WOULD fail these limits; no run serves one, and limits wide enough for
# every draw let a bfloat16 state through (REVIEW.md of PR 33).
# LIMIT_READINGS, (p90, median, largest) each, TPU v5e, the builder's chip
# runs of PR 33; benchmark/tests/test_ling_cell.py holds the limits to
# them:
#   "change": the check as a run makes it (the traced run of the committed
#     cell, seed 2147490011): 0.147 / 0.0339.
#   "ref_bf16_state": the REFERENCE with the linear layers' state rounded
#     to bfloat16 after every token, over the rows that very run served
#     (tools/olmoe_reference_probe.py --served-from ... --then-bf16-state):
#     0.235 / 0.0542. The state is this cell's new mechanism, 1.9 GB a
#     step; a served path that stored it in bfloat16 would halve that
#     traffic, and must not come out `correct`.
#   "ref_float8": the REFERENCE with its weights rounded to float8 (e4m3),
#     the nearest precision below the configuration's matmuls, over rows
#     served alone: 1.04 / 0.507.
# So: p90 0.19, 1.29x the change, and the bfloat16-state reference is 1.24x
# past it; median 0.043, 1.27x the change, and the bfloat16-state reference
# is 1.26x past it. Both controls fail BOTH limits.
# "alone": earlier readings of the same three prompts served with no row
# beside them (b = 1, which never reached kda_mix's split of a step's
# rows), by draw and chunk cap: what a draw and a chunk geometry move.
# (A first reading of the bfloat16-state control read the sound numbers to
# the digit: XLA drops a float32 -> bfloat16 -> float32 cast pair;
# benchmark/reference/ling.py rounds with `lax.reduce_precision`.)
# What the limits do not catch at bfloat16 is anything smaller than a
# bfloat16 state; the float32 tier-1 test holds every listed mutation at a
# thousand times its limit or more. float32 has not been read on a chip.
LIMIT_READINGS = {
    "change": (0.14711251258850097, 0.03389263153076172,
               0.48587560653686523),
    "ref_bf16_state": (0.23493285179138185, 0.05416154861450195,
                       0.6095552444458008),
    "ref_float8": (1.0425009250640869, 0.5069785118103027,
                   1.8814196586608887),
    "alone": {"4242@512": (0.13222403526306153, 0.0317080020904541,
                           0.5253481864929199),
              "4242@256": (0.1511, 0.03191, 0.430),
              "777@512": (0.2108165740966797, 0.06155991554260254,
                          0.8241190910339355),
              "31337@512": (0.15737581253051758, 0.04070854187011719,
                            0.7146353721618652),
              "4242@512 ref_bf16_state": (0.23793392181396483,
                                          0.04586148262023926,
                                          0.5950427055358887)},
}
LIMITS = {"bfloat16": (0.19, 0.043), "float32": (2e-3, 5e-4)}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def applies(config_meta: dict) -> bool:
    return (config_meta.get("reference_check") or {}).get("module") == "ling"


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shared():
    """checks/reference_logits.py, a private copy with this check's
    prompt lengths."""
    mod = _load("bench_check_reference_logits_for_ling", "checks",
                "reference_logits.py")
    mod.PROMPTS = PROMPTS
    if SEED is not None:
        mod.SEED = SEED
    return mod


async def served_rows(ctx) -> list:
    """The shared check's three requests (its `served_rows`), sent while
    HOLDERS greedy rows decode: one holder first, the others together once
    it streams (they join it in one mixed step), the compared prompts when
    all stream. A holder that ended before the last compared request did
    is an error: the rows beside it were fewer than the check says."""
    from harness import loadgen, traffic
    n_prompt, n_out = HOLDER_TOKENS
    base = shared()

    def holder(i):
        seed = 9100 + i
        req = {"prompt_tokens": n_prompt, "max_tokens": n_out, "seed": seed,
               "sampling": {"temperature": 0.0},
               "extra": {"logprobs": True, "top_logprobs": base.TOP},
               "content": traffic.prompt_words(
                   random.Random(seed), n_prompt - ctx.template_tokens,
                   ctx.vocab)}
        streams = asyncio.Event()
        return streams, asyncio.create_task(loadgen.do_request(
            ctx.served.port, ctx.model, req, loadgen.Row(), streams))

    holders = [holder(0)]
    await holders[0][0].wait()
    holders += [holder(i) for i in range(1, HOLDERS)]
    try:
        for streams, _ in holders:
            await streams.wait()
        out = await base.served_rows(ctx)
        done = time.monotonic()
    finally:
        rows = await asyncio.gather(*(task for _, task in holders))
    for row in rows:
        if row.get("status") != 200 or row.get("error") \
                or len(row["frames"]) != n_out:
            raise RuntimeError(
                f"a holder failed: {row.get('status')} {row.get('error')} "
                f"{len(row['frames'])} of {n_out} tokens")
        if row["end"] <= done:
            raise RuntimeError("a holder ended before the compared "
                               "requests did: fewer rows beside them")
    return out


def differences(served: list, params, hf: dict, ref, cast=None,
                state_dtype=None) -> list:
    """|served - reference| for every served log-probability; the
    reference's head is applied at the compared rows only."""
    import jax.numpy as jnp
    import numpy as np
    diffs = []
    for ids, ents in served:
        seq = ids + [c for c, _, _ in ents]
        width = -(-len(seq) // 8) * 8
        padded = jnp.asarray(seq + [0] * (width - len(seq)), jnp.int32)
        # causal, and the recurrence runs forward: the padding behind the
        # sequence reaches no position of it; row len(ids) - 1 + i
        # predicts generated token i
        rows = [len(ids) - 1 + i for i in range(len(ents))]
        logp = np.asarray(ref.forward_blocked(
            params, padded, hf, positions=rows, cast=cast,
            state_dtype=jnp.dtype(state_dtype or "float32")))
        for at, (chosen, lp, tops) in zip(logp, ents):
            diffs.append(abs(lp - float(at[chosen])))
            diffs += [abs(v - float(at[t])) for t, v in tops]
    return diffs


def readings(diffs: list) -> dict:
    """What is compared (median, p90) and what is only reported."""
    return {"median": statistics.median(diffs),
            "p90": statistics.quantiles(diffs, n=10)[-1],
            "largest": max(diffs), "values": len(diffs)}


def problems(got: dict) -> list:
    """THE comparison: a reading of `measure` against LIMITS, as strings;
    empty when it passes."""
    if not all(math.isfinite(got[k]) for k in ("largest", "p90", "median")):
        return ["non-finite difference from the reference"]
    p90, median = LIMITS[got["dtype"]]
    bad = []
    if got["p90"] >= p90:
        bad.append(f"90th percentile of |logprob - reference| "
                   f"{got['p90']:.4f} >= {p90}")
    if got["median"] >= median:
        bad.append(f"median |logprob - reference| {got['median']:.5f} "
                   f">= {median}")
    return bad


def served_path(ctx) -> str:
    """Where a run leaves what it served (ids and log-probabilities): in
    its output directory, which holds model/<name>/."""
    return os.path.join(
        os.path.dirname(os.path.dirname(ctx.served.model_dir)),
        "reference_logits_ling.served.json")


async def measure(ctx, cast=None, keep: list = None,
                  state_dtype=None, served: list = None) -> dict:
    """Serve, run the reference, return the readings; `keep` (a list) is
    extended with the 432 differences themselves. `cast` (on every weight
    leaf) and `state_dtype` (the recurrence's state) change the REFERENCE
    alone: the two controls. `served`: rows an earlier run left at
    `served_path` (a control then costs one reference pass and no
    serving: tools/olmoe_reference_probe.py --served-from)."""
    with open(os.path.join(ctx.served.model_dir, "config.json")) as f:
        hf = json.load(f)
    ref = _load("bench_reference_ling", "reference", "ling.py")
    t0 = time.monotonic()
    if served is None:
        served = await served_rows(ctx)
        with open(served_path(ctx), "w") as f:
            json.dump(served, f)
    t1 = time.monotonic()
    engine = ctx.served.worker.engine
    diffs = await asyncio.get_running_loop().run_in_executor(
        None, differences, served, engine.params, hf, ref, cast,
        state_dtype)
    if keep is not None:
        keep.extend(diffs)
    return {**readings(diffs), "dtype": engine.model_cfg.dtype,
            "served_s": t1 - t0, "reference_s": time.monotonic() - t1}


async def run(ctx) -> list:
    """Problems found, as strings; empty when the check passes."""
    try:
        got = await measure(ctx)
    except RuntimeError as e:
        return [str(e)]
    print(f"[bench] reference_logits_ling: {json.dumps(got)}",
          flush=True, file=sys.stderr)
    return problems(got)
