"""Set-up check: Mellum's served path against its plain reference, on the
chip, at the published widths, at the TIMED context lengths and through the
TIMED programs, on log-probabilities and not on sampled tokens.

What is compared rides the steps the window is made of, not one-row
programs: first five fillers of the cell's own length (sampled as the cell
samples, each started when the last streamed its first token, as the
warm-up starts its holders) fill five of the eight slots; then three seeded
greedy prompts of 40, 1140 and 3315 tokens (inside the window; past it, so
that the sliding layers' table starts mid-context; the cell's own length,
three windows deep) are sent one after another with `logprobs` and the
most `top_logprobs` the frontend gives (8), 128 tokens each. Two fillers
end inside the compared span and their clients send the next request at
once, as a closed loop does. So the compared tokens come from `[8,64]`
mixed steps beside a neighbour's chunk (their own admissions too: the 1140
and 3315 prompts are prefilled 64 tokens a step beside 6 and 7 decoders)
and from full 8-row decode windows, each sliding layer gathering the short
table of the pages its row still holds in the window pool at that row's
own offset, each full layer the whole page list; every row hands pages of
the window pool back as it goes (the two long compared rows twice each
inside their 128 tokens: positions 1151 and 1215, 3327 and 3391), and a
row admitted later takes pages that another row released. `measure`
reports how many mixed steps and window steps the engine ran in the
compared span and how many pages went back, and `problems` refuses a span
that was not made of both kinds of step: a check that fell back to one
row at a time would say so, not pass. The fillers ask for logprobs too, so
that the check dispatches logprobs variants alone and leaves the warm-up
walk's programs to the walk (FILLER_EXTRA below). What no logprobs request
can reach is a window dispatched ahead of the last one's commit: the
engine chains windows only where no row wants logprobs.

The reference (`benchmark/reference/mellum.py`: float32, `highest` matmul
precision, no cache, the window as a mask over the whole sequence, RoPE by
layer kind with YaRN on the full layers) then runs one full forward pass a
prompt over prompt + generated tokens from the engine's own weight arrays,
a KV head's query heads and a block of experts at a time, and applies the
head and the log-softmax at the 128 compared positions only. Every served
value at the served ids is compared: 3 x 128 x (8 + 1) = 3456 numbers.

Three readings of the 3456 |differences|: the median and the 90th
percentile, which are held to limits, and the largest, which is reported
beside them and held to none (a maximum over flipped experts, as in
checks/reference_logits_moonlight.py, whose statistics these are). A
failure makes the run not `correct`.

Only where the configuration's `meta.json` has a `reference_check` key
whose module is "mellum". This file serves its own rows:
`checks/reference_logits.py`'s `served_rows` awaits one request after
another, which holds ONE slot live at a time.
"""
from __future__ import annotations

import asyncio
import importlib.util
import json
import math
import os
import random
import re
import statistics
import sys
import time

PROMPTS = (40, 1140, 3315)
N_TOKENS = 128
TOP = 8
SEED = 4242
# (prompt tokens, max_tokens) of each filler's FIRST request, then of every
# later one. Prompts are multiples of 256, whole chunks beside any number
# of decoders (a remainder chunk is one more program to load in every
# run's set-up); prompt + max_tokens lies in 3457..4096, the cell's one
# admission bucket. The first two end inside the compared span (the last
# compared row streams its first token ~190 and ~175 tokens into them):
# their clients' next requests are prefilled beside 7 decoders.
FILLERS = ((3328, 200), (3328, 280), (3328, 512), (3584, 512), (3328, 512))
FILLER_NEXT = (3328, 512)
SAMPLED = {"temperature": 0.7, "top_p": 0.95}     # the cell's sampling
# The fillers ask for their tokens' log-probabilities too (and read none):
# the engine keys a program on whether ANY row of the step wants them, so
# every program this check dispatches is a logprobs variant and none is
# one the warm-up walk or the window uses. The walk then loads its own
# programs, as in every other cell: where this check had loaded them for
# it (PR 38's review round, call 5), the walk took 38 s for 60, the closed
# loop's clients met the window in another phase and six runs read
# `output_tok_s` 254-287 where they had read 272-279 (PERF.md section 6).
FILLER_EXTRA = {"logprobs": True}
# the compared span has to hold both kinds of step (of ~145 and ~50)
MIN_STEPS = {"mixed_steps": 64, "window_steps": 16}

# (90th percentile, median) of |served - reference| over the 3456
# log-probabilities. The weights are the same bfloat16 values on both
# sides; the served path rounds every activation, the stored K and V rows
# and each projection's output to bfloat16 (relative 2**-9 a rounding,
# compounding over 12 layers). What decides the LARGEST reading is the
# router, as for Moonlight: 64 softmax scores of a seeded random gate lie
# close together, a bfloat16-sized change of the input flips the 8th and
# 9th expert of a token in a fair share of (token, layer) pairs, and a
# flipped expert weighs a renormalised ~1/8 of one expert's output. A
# maximum over such flips is heavy-tailed, so `largest` is printed and
# limited by nothing, and the cell is held on the two statistics that a
# handful of flipped positions cannot move.
# The readings the limits rest on (TPU v5e, the builder's chip run of
# PR 38's review round, call 6, the committed files with THESE limits:
# mellum2-12b-a2.5b at 12 layers, tools/olmoe_reference_probe.py --config
# mellum2-12b-a2.5b --prompt-seeds 4242,777,31337 --then-float8; every
# reading's span held 129 mixed steps, 96 window steps and 127 released
# pages):
#   the change, three draws: p90 0.0701 / 0.0723 / 0.0733, median 0.0184 /
#   0.0224 / 0.0229 (largest 0.272 / 0.323 / 0.385), `passes` true; every
#   run of the cell reads the first draw again (0.0701 / 0.0184 in all
#   seven runs of call 6);
#   the REFERENCE with its weights rounded to float8 (e4m3), the nearest
#   precision below the configuration's: p90 0.4218, median 0.1625
#   (largest 0.996), `passes` false, both limits named.
# The one-row check these replace (16 tokens a prompt, each request alone)
# read p90 0.063-0.079 and median 0.018-0.026: a row's logits do not
# depend on its neighbours, and the later tokens' longer run of their own
# bfloat16 K and V rows moves the statistics little.
# A fifth of Moonlight's readings on both sides: a renormalised softmax
# over 64 gives the 8th and 9th expert of a token weights that are close
# to each other, so a flip moves little, where Moonlight's sigmoid scores
# times 2.446 did not.
# So: p90 0.17, 2.3x the change's worst draw, and the float8 reference
# fails it by 2.5x (the two readings lie 5.8x apart); median 0.065, 2.8x
# the worst draw, and the float8 reference fails it by 2.5x (7.1x apart).
# benchmark/tests/test_mellum_cell.py holds `problems` below to these
# recorded readings. What the limits do not catch at bfloat16 is anything
# smaller than a bfloat16 rounding of every weight; the float32 tier-1
# test (tests/test_mellum.py) holds every listed mutation at nine
# thousand times its limit or more. float32 has not been read on a chip.
LIMITS = {"bfloat16": (0.17, 0.065), "float32": (2e-3, 5e-4)}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN = {"mixed_steps": "llm_engine_steps_mixed",
        "window_steps": "llm_engine_window_steps_total",
        "pages_released": "llm_engine_kv_window_pages_released_total"}


def applies(config_meta: dict) -> bool:
    return (config_meta.get("reference_check") or {}).get("module") \
        == "mellum"


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def token_id(piece: str) -> int:
    return int(re.search(r"w(\d+)", piece).group(1))


async def served_rows(ctx) -> tuple:
    """([(prompt ids, [(chosen id, logprob, [(id, logprob)] * TOP)] * N)],
    what the engine counted in the compared span), the compared rows
    served beside five sampled fillers of the cell's length."""
    from harness import traffic
    from harness.loadgen import Row, do_request
    from tokenizers import Tokenizer
    tok = Tokenizer.from_file(os.path.join(ctx.served.model_dir,
                                           "tokenizer.json"))
    port, model = ctx.served.port, ctx.model

    def words(prompt_tokens: int, seed: int) -> str:
        return traffic.prompt_words(
            random.Random(seed), prompt_tokens - ctx.template_tokens,
            ctx.vocab)

    async def send(prompt_tokens, max_tokens, seed, sampling, row, first,
                   extra=None) -> Row:
        req = {"prompt_tokens": prompt_tokens, "max_tokens": max_tokens,
               "seed": seed, "sampling": sampling, "extra": extra or {},
               "content": words(prompt_tokens, seed)}
        await do_request(port, model, req, row, first)
        if row.get("status") != 200 or row.get("error"):
            raise RuntimeError(f"request of {prompt_tokens} tokens failed: "
                               f"{row.get('status')} {row.get('error')}")
        return row

    async def filler(k: int, first: asyncio.Event) -> None:
        """A closed-loop client: its next request when the last ends."""
        shape, n = FILLERS[k], 0
        while True:
            await send(*shape, SEED + 1000 * (k + 1) + n, SAMPLED, Row(),
                       first if n == 0 else None, FILLER_EXTRA)
            shape, n = FILLER_NEXT, n + 1

    async def start(coro_of) -> asyncio.Task:
        """The task, once its first token streamed (or it failed)."""
        first = asyncio.Event()
        task = asyncio.create_task(coro_of(first))
        await first.wait()
        return task

    fillers, compared, rows = [], [], []
    try:
        for k in range(len(FILLERS)):
            fillers.append(await start(lambda first: filler(k, first)))
        before = await ctx.served.prom()
        for n, prompt_tokens in enumerate(PROMPTS):
            rows.append(Row(logprobs=[]))
            compared.append(await start(lambda first: send(
                prompt_tokens, N_TOKENS, SEED + n, {"temperature": 0.0},
                rows[-1], first,
                {"logprobs": True, "top_logprobs": TOP})))
        await asyncio.gather(*compared)
        after = await ctx.served.prom()
        for task in fillers:
            if task.done():     # a filler only ever ends by failing
                task.result()
    finally:
        for task in fillers + compared:
            task.cancel()
        await asyncio.gather(*fillers, *compared, return_exceptions=True)
    # the fillers' streams were cut: the engine drops their rows at its
    # next step; nothing of them may ride into the next check
    for _ in range(600):
        if not (await ctx.served.engine_metrics())["request_active_slots"]:
            break
        await asyncio.sleep(0.05)
    else:
        raise RuntimeError("the engine still holds the fillers' rows")
    out = []
    for n, (prompt_tokens, row) in enumerate(zip(PROMPTS, rows)):
        ids = tok.encode(f"w3 {words(prompt_tokens, SEED + n)} w4").ids
        if len(ids) != row["usage"]["prompt_tokens"]:
            raise RuntimeError(
                f"{len(ids)} prompt ids reconstructed, the server counted "
                f"{row['usage']['prompt_tokens']}")
        ents = row["logprobs"]
        if len(ents) != N_TOKENS:
            raise RuntimeError(f"{len(ents)} logprob entries for "
                               f"{N_TOKENS} tokens")
        out.append((ids, [
            (token_id(e["token"]), float(e["logprob"]),
             [(token_id(t["token"]), float(t["logprob"]))
              for t in e["top_logprobs"]]) for e in ents]))
    span = {key: after.get(name, 0.0) - before.get(name, 0.0)
            for key, name in SPAN.items()}
    return out, span


def differences(served: list, params, hf: dict, ref, cast=None) -> list:
    """|served - reference| for every served log-probability; the
    reference's head is applied at the compared rows only."""
    import jax.numpy as jnp
    import numpy as np
    diffs = []
    for ids, ents in served:
        seq = ids + [c for c, _, _ in ents]
        width = -(-len(seq) // 8) * 8
        padded = jnp.asarray(seq + [0] * (width - len(seq)), jnp.int32)
        # causal: the padding behind the sequence reaches no position of
        # it; row len(ids) - 1 + i predicts generated token i
        rows = [len(ids) - 1 + i for i in range(len(ents))]
        logp = np.asarray(ref.forward_blocked(params, padded, hf,
                                              positions=rows, cast=cast))
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
    """THE comparison: a reading of `measure` against LIMITS and
    MIN_STEPS, as strings; empty when it passes."""
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
    for key, least in MIN_STEPS.items():
        if got[key] < least:
            bad.append(f"the compared span held {got[key]:.0f} {key}, "
                       f"under {least}: its tokens did not ride the "
                       f"timed programs")
    return bad


async def measure(ctx, cast=None, keep: list = None) -> dict:
    """Serve, run the reference, return the readings; `keep` (a list) is
    extended with the differences themselves."""
    with open(os.path.join(ctx.served.model_dir, "config.json")) as f:
        hf = json.load(f)
    ref = _load("bench_reference_mellum", "reference", "mellum.py")
    t0 = time.monotonic()
    served, span = await served_rows(ctx)
    t1 = time.monotonic()
    engine = ctx.served.worker.engine
    diffs = await asyncio.get_running_loop().run_in_executor(
        None, differences, served, engine.params, hf, ref, cast)
    if keep is not None:
        keep.extend(diffs)
    return {**readings(diffs), **span, "dtype": engine.model_cfg.dtype,
            "served_s": t1 - t0, "reference_s": time.monotonic() - t1}


async def run(ctx) -> list:
    """Problems found, as strings; empty when the check passes."""
    try:
        got = await measure(ctx)
    except RuntimeError as e:
        return [str(e)]
    print(f"[bench] reference_logits_mellum: {json.dumps(got)}",
          flush=True, file=sys.stderr)
    return problems(got)
