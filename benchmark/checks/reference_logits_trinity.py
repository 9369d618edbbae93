"""Set-up check: Trinity-Mini's served path against its plain reference, on
the chip, at the published widths, at the TIMED context lengths and through
the TIMED programs, on log-probabilities and not on sampled tokens.

After the pattern of checks/reference_logits_mellum.py (whose statistics
and whose way of filling the slots these are). What is compared rides the
steps the window is made of, not one-row programs: first five fillers of
the cell's own length (sampled as the cell samples, each started when the
last streamed its first token, as the warm-up starts its holders) fill
five of the eight slots; then three seeded greedy prompts of 40, 2200 and
3315 tokens (inside the 2048-token window; just past it, so that the
sliding layers' table starts mid-context and pages go back inside the
compared span; the cell's own length, 1.6 windows deep) are sent one after
another with `logprobs` and the most `top_logprobs` the frontend gives
(8), 128 tokens each. Two fillers end inside the compared span and their
clients send the next request at once, as a closed loop does. So the
compared tokens come from `[8,64]` mixed steps beside a neighbour's chunk
(their own admissions too: the 2200 and 3315 prompts are prefilled 64
tokens a step beside 6 and 7 decoders) and from full 8-row decode windows:
the lead's layer and each sliding layer of the period gathering the short
table of the pages its row still holds in the window pool at that row's
own offset, the full layer the whole page list and attending WITHOUT
positions; every layer's attention through the head norms and the output
gate; the lead's dense MLP before the period loop, then the sigmoid router,
128 experts and the shared one. Every row hands pages of the window pool
back as it goes (the two long compared rows twice each inside their 128
tokens: positions 2239 and 2303, 3327 and 3391), and a row admitted later
takes pages that another row released. `measure` reports how many mixed
steps and window steps the engine ran in the compared span and how many
pages went back, and `problems` refuses a span that was not made of both
kinds of step: a check that fell back to one row at a time would say so,
not pass. The fillers ask for logprobs too, so that the check dispatches
logprobs variants alone and leaves the warm-up walk's programs to the walk
(FILLER_EXTRA below). Before the first filler a PILOT holds a row (a
short prompt under a long `max_tokens`, cut when the first filler streams
its first token), so that the first filler's long prompt is prefilled
beside a decoder at the decoder's admission width and not alone up the
ladder of page-table widths: six programs less to compile in a cold
set-up (PILOT below). What no logprobs request can reach is a window
dispatched ahead of the last one's commit: the engine chains windows only
where no row wants logprobs.

The reference (`benchmark/reference/trinity.py`: float32, `highest` matmul
precision, no cache, the window as a mask over the whole sequence, RoPE on
the sliding layers alone) then runs one full forward pass a prompt over
prompt + generated tokens from the engine's own weight arrays, a KV head's
query heads and a block of experts at a time, and applies the head and the
log-softmax at the 128 compared positions only. Every served value at the
served ids is compared: 3 x 128 x (8 + 1) = 3456 numbers.

Three readings of the 3456 |differences|: the median and the 90th
percentile, which are held to limits, and the largest, which is reported
beside them and held to none (a maximum over flipped experts, as in
checks/reference_logits_moonlight.py). A failure makes the run not
`correct`.

Only where the configuration's `meta.json` has a `reference_check` key
whose module is "trinity". This file serves its own rows, as the Mellum
check does.
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

PROMPTS = (40, 2200, 3315)
N_TOKENS = 128
TOP = 8
SEED = 4242
# (prompt tokens, max_tokens) of each filler's FIRST request, then of every
# later one. Prompts are multiples of 256, whole chunks beside any number
# of decoders (a remainder chunk is one more program to load in every
# run's set-up); prompt + max_tokens lies in 3457..4096, the cell's one
# admission bucket. The first two end inside the compared span (the last
# compared row streams its first token ~205 and ~190 tokens into them):
# their clients' next requests are prefilled beside 7 decoders.
FILLERS = ((3328, 200), (3328, 280), (3328, 512), (3584, 512), (3328, 512))
FILLER_NEXT = (3328, 512)
# (prompt tokens, max_tokens) of the pilot: the row that decodes while the
# first filler is prefilled, and is cut at that filler's first token. A
# long prompt prefilled ALONE takes a program a page-table width as its
# pages grow (`[1,512]` at 8, 16, 24, 32 and 48 pages, `[1,256]` at 64:
# six logprobs programs at 36-43 s each that nothing else ever runs, and
# the driver's run of PR 40 was cut at its 1200 s with them). A step that
# holds a DECODE row takes that row's admission width instead (prompt +
# max_tokens, `Scheduler._build_prefill`): 34 + 3200 tokens are 51 pages, the
# cell's one bucket of 64, so the first filler's thirteen chunks ride the
# `[2,256]` program at 64 pages, which the second filler's take anyway.
# The pilot's own prefill is served_logprobs.py's program (`[1,64]` at
# one page), and its few windows alone are ONE program more (8 steps at
# 64 pages over one live page): its prompt is short so that a window
# which holds it ALONE still starts inside its first page when the first
# filler's request has arrived (windows from 35, 43, 51 and 59 tokens;
# the request is sent at the pilot's first token; beside the filler the
# live width is the filler's). Tried before
# and taken back (PR 40, call 4): a short first request under a SHORT
# `max_tokens`, which walked the same widths at `[2,256]` and dispatched
# two programs more.
PILOT = (34, 3200)
SAMPLED = {"temperature": 0.7, "top_p": 0.95}     # the cell's sampling
# The fillers ask for their tokens' log-probabilities too (and read none):
# the engine keys a program on whether ANY row of the step wants them, so
# every program this check dispatches is a logprobs variant and none is
# one the warm-up walk or the window uses. The walk then loads its own
# programs, as in every other cell (where the Mellum check once loaded
# them for it, the closed loop's clients met the window in another phase
# and six runs spread 8 %: PERF.md section 6, PR 38).
FILLER_EXTRA = {"logprobs": True}
# the compared span has to hold both kinds of step (of ~145 and ~50)
MIN_STEPS = {"mixed_steps": 64, "window_steps": 16}

# (90th percentile, median) of |served - reference| over the 3456
# log-probabilities. The weights are the same bfloat16 values on both
# sides; the served path rounds every activation, the stored K and V rows
# and each projection's output to bfloat16 (relative 2**-9 a rounding,
# compounding over 5 layers of four norms each). What decides the LARGEST
# reading is the router, as for Moonlight: 128 sigmoid scores of a seeded
# random gate lie close together, a bfloat16-sized change of the input
# flips the 8th and 9th expert of a token in a fair share of (token,
# layer) pairs, and a flipped expert weighs a renormalised ~1/8 x 2.826 of
# one expert's output. A maximum over such flips is heavy-tailed, so
# `largest` is printed and limited by nothing, and the cell is held on the
# two statistics that a handful of flipped positions cannot move.
# The readings the limits rest on (TPU v5e, the builder's chip run of
# PR 40, call 1: trinity-mini at 1 + 4 layers, tools/
# olmoe_reference_probe.py --config trinity-mini --prompt-seeds
# 4242,777,31337 --then-float8; every reading's span held 141 mixed steps,
# 96-104 window steps and 81 released pages; RECORDED below has them
# unrounded, and benchmark/tests/test_trinity_cell.py holds `problems` to
# them):
#   the change, three draws: p90 0.1475 / 0.1153 / 0.1485, median 0.0143 /
#   0.0129 / 0.0150 (largest 0.778 / 0.853 / 0.914); every run of the cell
#   reads the first draw again (0.1475 / 0.0143 in call 1's three runs);
#   the REFERENCE with its weights rounded to float8 (e4m3), the nearest
#   precision below the configuration's: p90 0.6202, median 0.2482
#   (largest 1.455).
# Between Mellum's readings (0.07 / 0.02: a renormalised softmax over 64)
# and Moonlight's (0.36-0.52 / 0.06-0.08: sigmoid scores x 2.446 over 9
# layers): the median is Mellum's because each half's output is normed
# before the residual and only 4 layers hold experts; the 90th percentile
# is twice Mellum's because a flipped expert weighs ~1/8 x 2.826.
# So: p90 0.30, 2.0x the change's worst draw, and the float8 reference
# fails it by 2.1x (the two readings lie 4.2x apart); median 0.06, 4.0x
# the worst draw, and the float8 reference fails it by 4.1x (16.5x apart).
# The float8 reference fails BOTH. What the limits do not catch at
# bfloat16 is anything smaller than a bfloat16 rounding of every weight;
# the float32 tier-1 test (tests/test_trinity.py) holds every listed
# mutation at eighteen thousand times its limit or more, and the CPU
# rehearsal served in float32 (tools/olmoe_reference_probe.py --rehearsal
# --float32) reads 1.4e-6 / 4.8e-7 sound, 0.148 / 0.056 with the full
# layers rotated and 1.09 / 0.41 with the gate skipped, against the
# float32 limits below. float32 has not been read on a chip.
LIMITS = {"bfloat16": (0.30, 0.06), "float32": (2e-3, 5e-4)}
RECORDED = {
    "span": {"mixed_steps": 141.0, "window_steps": 96.0,
             "pages_released": 81.0},
    "sound": [{"median": 0.014312744140625, "p90": 0.14751338958740234,
               "largest": 0.7777385711669922},
              {"median": 0.01291036605834961, "p90": 0.1152613639831543,
               "largest": 0.8530473709106445},
              {"median": 0.014962196350097656, "p90": 0.14845075607299804,
               "largest": 0.9143571853637695}],
    "float8": {"median": 0.24819326400756836, "p90": 0.6202096939086914,
               "largest": 1.4549970626831055}}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN = {"mixed_steps": "llm_engine_steps_mixed",
        "window_steps": "llm_engine_window_steps_total",
        "pages_released": "llm_engine_kv_window_pages_released_total"}


def applies(config_meta: dict) -> bool:
    return (config_meta.get("reference_check") or {}).get("module") \
        == "trinity"


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

    async def live_rows() -> int:
        return (await ctx.served.engine_metrics())["request_active_slots"]

    fillers, compared, rows = [], [], []
    pilot = None
    try:
        pilot = await start(lambda first: send(
            *PILOT, SEED + 500, SAMPLED, Row(), first, FILLER_EXTRA))
        if pilot.done():        # it only ever ends here by failing
            pilot.result()
        for k in range(len(FILLERS)):
            fillers.append(await start(lambda first: filler(k, first)))
            if k == 0:
                # the pilot's stream is cut and the engine drops its row
                # at its next step: the second filler is prefilled beside
                # the first alone, as it was before there was a pilot
                pilot.cancel()
                await asyncio.gather(pilot, return_exceptions=True)
                for _ in range(600):
                    if await live_rows() <= 1:
                        break
                    await asyncio.sleep(0.05)
                else:
                    raise RuntimeError("the engine still holds the pilot")
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
        tasks = fillers + compared + [pilot] * (pilot is not None)
        for task in tasks:
            task.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    # the fillers' streams were cut: the engine drops their rows at its
    # next step; nothing of them may ride into the next check
    for _ in range(600):
        if not await live_rows():
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
    # ONE padded width for the three sequences: the reference's programs
    # are compiled a width, and a width of its own a prompt is three sets
    # of them in the compile cache
    width = -(-max(len(ids) + len(ents) for ids, ents in served) // 8) * 8
    for ids, ents in served:
        seq = ids + [c for c, _, _ in ents]
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
    ref = _load("bench_reference_trinity", "reference", "trinity.py")
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
    print(f"[bench] reference_logits_trinity: {json.dumps(got)}",
          flush=True, file=sys.stderr)
    return problems(got)
