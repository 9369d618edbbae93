"""Set-up check: LFM2-8B-A1B's served path against its plain reference, on
the chip, at the published widths, at the TIMED context lengths and through
the TIMED programs: on log-probabilities, and on the STATE itself.

After the pattern of checks/reference_logits_trinity.py (the same traffic
mix: its pilot, its fillers and its statistics) and of
checks/reference_logits_falcon_h1.py (a state read back out of the engine's
cache). What is compared rides the steps the window is made of: first a
pilot, then five fillers of the cell's own length (sampled as the cell
samples, each started when the last streamed its first token) fill five of
the eight slots; then three seeded greedy prompts of 40, 1140 and 3329
tokens are sent one after another with `logprobs` and the most
`top_logprobs` the frontend gives (8), 128 tokens each. So the compared
tokens come from `[8,64]` mixed steps beside a neighbour's chunk (their own
admissions too: the 1140 and 3329 prompts are prefilled 64 tokens a step
beside 6 and 7 decoders, every conv layer carrying its tail over 17 and 52
chunk edges beside one-token rows in the same step) and from 8-row decode
windows (the tails in the step scan's carry beside the attention layers'
new K / V rows). 3329 = 52 x 64 + 1: the last chunk of that prompt holds ONE
token, a chunk row that takes the one-token form, and the sequence's last
fed position is 3329 + 127 = 54 x 64, a chunk edge (why: `CONTROLS`,
ref_lost_tail).

The state: the last compared sequence's slot is found at its first token
(the running sequence whose prompt is its ids); once it has streamed, the
fillers' clients send nothing more (a finished sequence's slot is the next
one handed out, `StateSlots` is a stack), the streams are cut, and when the
engine has drained all 11 conv layers' `[2, 2048]` tails of that slot are
read out of `engine.cache["conv_tail"]` and compared with the reference's
last two rows of B * u after the same tokens (or after one more: a decode
window that emitted the last token before its own last step feeds it too;
`nearest_state` tells which, the two lie a whole row apart).

The reference (`benchmark/reference/lfm2.py`: float32, `highest` matmul
precision, no cache, no chunks, the convolution as the three-tap sum over
the whole sequence) runs one full forward pass a prompt over prompt +
generated tokens from the engine's own weight arrays, a KV head's query
heads and a block of experts at a time, and applies the head (the embedding
table) and the log-softmax at the 128 compared positions only: 3 x 128 x
(8 + 1) = 3456 numbers.

Only where the configuration's `meta.json` has a `reference_check` key
whose module is "lfm2".
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

PROMPTS = (40, 1140, 3329)
N_TOKENS = 128
TOP = 8
SEED = 4242
# checks/reference_logits_trinity.py's, for the same mix and the same
# reasons: whole 256-token chunks, prompt + max_tokens inside the cell's one
# admission bucket (3457..4096), the first two ending inside the compared
# span; the pilot that keeps the first filler's prefill off the ladder of
# page-table widths; the fillers asking for logprobs so that every program
# this check dispatches is a logprobs variant and the warm-up walk loads
# its own
FILLERS = ((3328, 200), (3328, 280), (3328, 512), (3584, 512), (3328, 512))
FILLER_NEXT = (3328, 512)
PILOT = (34, 3200)
SAMPLED = {"temperature": 0.7, "top_p": 0.95}     # the cell's sampling
FILLER_EXTRA = {"logprobs": True}
# the compared span has to hold both kinds of step
MIN_STEPS = {"mixed_steps": 64, "window_steps": 16}

# the controls: each changes the REFERENCE alone (tools/
# olmoe_reference_probe.py --config lfm2-8b-a1b --then-controls reads them
# over what one run served); `forward_blocked`'s keyword arguments by name
CONTROLS = {
    # the weights rounded to float8 (e4m3), the nearest precision below
    # the configuration's
    "ref_float8": dict(cast="float8_e4m3fn"),
    # every conv layer's tail lost at each 64-token edge: what a served
    # path that dropped the tail between chunks would compute. Its tokens
    # at an edge and one past it (2 of 64) are wrong in all 11 layers and
    # every later token reads them through the 3 attention layers; the
    # slot's own tail is wrong where the last fed position is an edge,
    # which is why the third prompt ends on one
    "ref_lost_tail": dict(reset_every=64),
    # the selection bias added to the picked experts' WEIGHTS too
    "ref_bias_in_weights": dict(bias_in_weights=True),
    # bfloat16 activations at the block's joints: the served path's own
    # precision, expected to pass (recorded as not seen)
    "ref_bf16_act": dict(act_dtype="bfloat16"),
}

# (90th percentile, median) of |served - reference| over the 3456
# log-probabilities, and (largest over the LEAD's conv layers, median over
# all 11 conv layers) of the tail's relative distance |served - reference|
# / |reference| (Frobenius over the [2, 2048] rows). The weights are the
# same bfloat16 values on both sides; the served path rounds every
# activation, the stored K / V rows, the stored tail and each projection's
# output to bfloat16. What decides every reading but the lead's is the
# ROUTER: 32 sigmoid scores of a seeded gate lie close together, a
# bfloat16-sized change of the input flips the 4th and 5th expert of a
# token in a fair share of (token, layer) pairs, a flipped expert carries a
# quarter of the block's output, and TWELVE layers route (Trinity has
# four, Moonlight eight): the sound median is 0.111 where Trinity reads
# 0.014 and Moonlight 0.06-0.08. `largest` is a maximum over such flips,
# printed and limited by nothing. A tail is two token rows: one flip of one
# of the two tokens in any layer before it moves that tail and every later
# one by a tenth of its norm (the change's tails read 0.004, 0.010, 0.015,
# 0.021, 0.026 and then 0.086 ... 0.240: a flip behind the second attention
# layer), so the state is held on TWO statistics: the lead's two tails,
# which NO router precedes (layers 0 and 1: the product of two projections
# of a bfloat16 input, rounded once more to be stored: 2**-8 a rounding),
# and the median over all 11, which a flip in the last layers cannot move.
# LIMIT_READINGS, TPU v5e, the builder's chip run of PR 50 (call 1: one
# served process, the controls over what it served; two runs of the cell
# read the same digits to the last): a reading = ((p90, median, largest)
# of the log-probabilities, (largest of the lead's 2, median of all 11) of
# the tails' distances); benchmark/tests/test_lfm2_cell.py holds the
# limits to them:
#   "change": 0.349 / 0.111; the tails 0.0096 / 0.086.
#   "ref_float8": the REFERENCE with its weights rounded to float8 (e4m3),
#     the nearest precision below the configuration's: 1.92 / 1.03, nine
#     times the change's median; the tails 0.189 / 0.60. Fails all four.
#   "ref_lost_tail": the REFERENCE with every conv layer's tail lost at
#     each 64-token edge: 1.13 / 0.163 (two positions of 64 are wrong in
#     all 11 layers, and every later one reads them through 3 attention
#     layers: the 90th percentile sees it at 3.2 x the change, the median
#     at 1.46 x); the tails 1.61 / 1.74, since the last fed position is an
#     edge: 168 and 20 times the change. Fails all four.
#   "ref_bias_in_weights": the REFERENCE weighing the picked experts with
#     the selection bias too: 0.439 / 0.155, 1.26 x and 1.40 x the change
#     (a bias of 0.1 N(0,1) on scores near one half moves a renormalised
#     weight by a tenth, which is what a flipped expert moves a token by,
#     and flips are the change's own reading); the lead's tails are the
#     change's to the digit (no expert precedes them), the median of all
#     0.100. Fails the two log-probability limits, thinly, and the float32
#     tier-1 test holds the same leaf at 300 x its limit (tests/
#     test_lfm2.py, "no-expert-bias"; the router itself in
#     test_the_bias_moves_the_pick_and_not_the_weights).
# So: LIMITS, the geometric means of the change's reading and the nearest
# control's: p90 0.39 (1.12 x the change, the bias in the weights 1.12 x
# past it, the lost tail 2.9 x) and median 0.131 (1.18 x the change, the
# bias in the weights 1.18 x past it, the lost tail 1.24 x, float8 7.9 x).
# Thin on purpose: a limit with Falcon-H1's 2.2 x of room above the change
# would pass a model whose bias weighs. What keeps a thin limit from
# refusing a sound run: the reading is ONE draw (the prompts come from the
# fixed SEED, the weights from the engine's fixed seed, the compared
# requests are greedy, the fillers' sampling is seeded and the traffic's
# `--seed` moves nothing here) and it read the same 17 digits in a cold
# process and a warm one, whose steps were timed minutes apart. A PR that
# changes a program's arithmetic moves it and reads it again.
# STATE_LIMITS: 0.03 on the lead's tails (3.1 x the change; float8 6.3 x
# past it, the lost tail 54 x) and 0.25 on the median of all 11 (2.9 x the
# change; float8 2.4 x past it, the lost tail 7 x).
# CONTROLS_NOT_SEEN: the reference with bfloat16 ACTIVATIONS at the block's
# joints is the served path's own precision: 0.321 / 0.093 and 0.0095 /
# 0.087, under every limit, as ISSUE 50 expected. It does not read nearer
# the served path than the float32 reference does (0.349 / 0.111): two
# roundings of one function lie as far from each other as each lies from
# the function, and the flips they cause are their own.
# In float32 (the tiny rehearsal on the CPU, tools/olmoe_reference_probe.py
# --config lfm2-8b-a1b --rehearsal --float32 --then-controls) the change
# reads 4.8e-6 / 1.9e-6 and 9.2e-7 / 2.0e-6, and every control fails the
# float32 limits below (float8 1.33 / 0.54; the lost tail 0.68 / 0.075 and
# tails of 1.7; the bias 0.27 / 0.097; bfloat16 activations 0.23 / 0.067).
# float32 has not been read on a chip.
LIMIT_READINGS = {
    "change": ((0.34882187843322754, 0.11103653907775879,
                1.3090095520019531),
               (0.009618947426804612, 0.0858720949248815)),
    "ref_float8": ((1.9183323383331299, 1.0305325984954834,
                    3.4796195030212402),
                   (0.18899780861241222, 0.6006513179603422)),
    "ref_lost_tail": ((1.1334054470062256, 0.1625065803527832,
                       5.76644229888916),
                      (1.612136173826576, 1.7382509101169228)),
    "ref_bias_in_weights": ((0.4386013031005859, 0.15502047538757324,
                             1.1369991302490234),
                            (0.009618947426804612, 0.09988695771490563)),
}
CONTROLS_NOT_SEEN = {
    "ref_bf16_act": ((0.3212188720703125, 0.09278583526611328,
                      1.1955738067626953),
                     (0.009502610121284024, 0.08720675595497152)),
}
LIMITS = {"bfloat16": (0.39, 0.131), "float32": (2e-3, 5e-4)}
# (largest over the lead's conv layers, median over every conv layer) of
# the tail's relative distance from the reference's
STATE_LIMITS = {"bfloat16": (0.03, 0.25), "float32": (1e-4, 1e-4)}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPAN = {"mixed_steps": "llm_engine_steps_mixed",
        "window_steps": "llm_engine_window_steps_total"}
STATE_LEAF = "conv_tail"


def applies(config_meta: dict) -> bool:
    return (config_meta.get("reference_check") or {}).get("module") \
        == "lfm2"


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
    what the engine counted in the compared span, the state slot of the
    LAST compared sequence), the compared rows served beside five sampled
    fillers of the cell's length."""
    from harness import traffic
    from harness.loadgen import Row, do_request
    from tokenizers import Tokenizer
    tok = Tokenizer.from_file(os.path.join(ctx.served.model_dir,
                                           "tokenizer.json"))
    port, model = ctx.served.port, ctx.model
    closing = asyncio.Event()   # the fillers' clients send nothing more

    def words(prompt_tokens: int, seed: int) -> str:
        return traffic.prompt_words(
            random.Random(seed), prompt_tokens - ctx.template_tokens,
            ctx.vocab)

    def ids_of(prompt_tokens: int, seed: int) -> list:
        return tok.encode(f"w3 {words(prompt_tokens, seed)} w4").ids

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
        while not closing.is_set():
            await send(*shape, SEED + 1000 * (k + 1) + n, SAMPLED, Row(),
                       first if n == 0 else None, FILLER_EXTRA)
            shape, n = FILLER_NEXT, n + 1
        await asyncio.Event().wait()    # held until it is cancelled

    async def start(coro_of) -> asyncio.Task:
        """The task, once its first token streamed (or it failed)."""
        first = asyncio.Event()
        task = asyncio.create_task(coro_of(first))
        await first.wait()
        return task

    async def live_rows() -> int:
        return (await ctx.served.engine_metrics())["request_active_slots"]

    def slot_of(ids: list):
        def find(engine):
            return [seq.state_slot for seq in engine.scheduler.running
                    if seq is not None and list(seq.prompt) == ids]
        return ctx.served.worker.submit(find)

    fillers, compared, rows = [], [], []
    pilot, slot = None, None
    try:
        pilot = await start(lambda first: send(
            *PILOT, SEED + 500, SAMPLED, Row(), first, FILLER_EXTRA))
        if pilot.done():        # it only ever ends here by failing
            pilot.result()
        for k in range(len(FILLERS)):
            fillers.append(await start(lambda first: filler(k, first)))
            if k == 0:
                # the pilot's stream is cut and the engine drops its row
                # at its next step
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
            if n == len(PROMPTS) - 1:
                closing.set()
                found = await slot_of(ids_of(prompt_tokens, SEED + n))
                if len(found) != 1 or found[0] < 0:
                    raise RuntimeError(
                        f"the last compared sequence's state slot: {found}")
                slot = found[0]
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
        ids = ids_of(prompt_tokens, SEED + n)
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
    return out, span, slot


async def served_state(ctx, slot: int):
    """Every conv layer's tail in `slot`, [Lc, K - 1, D] float32 on the
    host, read between device steps once the engine has nothing left to do
    (its programs donate the cache)."""
    import numpy as np
    engine = ctx.served.worker.engine
    while engine.has_work():
        await asyncio.sleep(0.01)
    return await ctx.served.worker.submit(
        lambda eng: np.asarray(eng.cache[STATE_LEAF][:, slot], np.float32))


def state_distances(served, reference) -> list:
    """|served - reference| / |reference| (Frobenius, float64) of every
    conv layer's [K - 1, D] tail: [Lc]."""
    import numpy as np
    served, reference = (np.asarray(a, np.float64)
                         for a in (served, reference))
    norm = lambda a: np.sqrt((a * a).sum(axis=(-2, -1)))  # noqa: E731
    return (norm(served - reference) / norm(reference)).tolist()


def lead_conv_layers(hf: dict) -> int:
    """The conv layers that no router precedes: those among the dense lead
    and the first layer behind it (whose mixer reads the stream before its
    own experts do), first on the state's layer axis."""
    lead = int(hf.get("num_dense_layers") or 0)
    return sum(kind == "conv" for kind in hf["layer_types"][:lead + 1])


def nearest_state(served, reference, tokens: int, lead: int = 2) -> dict:
    """The served slot against the reference's tails after `tokens` tokens
    or after one more (`reference`: both, [2, Lc, K - 1, D]), whichever
    lies nearer, and `state_fed`, which that was. The two share one row of
    two and lie a whole row apart, so the choice can hide no fault. What
    is compared: the largest over the first `lead` conv layers
    (`lead_conv_layers`) and the median over all."""
    both = [state_distances(served, ref) for ref in reference]
    one_more = statistics.fmean(both[1]) < statistics.fmean(both[0])
    dist = both[one_more]
    return {"state_lead_largest": max(dist[:lead]),
            "state_median": statistics.median(dist),
            "state_by_layer": dist, "state_fed": tokens + one_more}


def differences(rows: list, params, hf: dict, ref, state_tokens=None,
                **control) -> tuple:
    """(|served - reference| for every served log-probability, the
    reference's tails): the reference's head is applied at the compared
    rows only. `state_tokens`: a count of the LAST row's tokens; with it
    the second value is every conv layer's tail after that many and after
    one more, [2, Lc, K - 1, D], else None. `control`: an entry of
    CONTROLS, dtypes and the cast by name."""
    import jax.numpy as jnp
    import numpy as np
    control = dict(control)
    if control.get("act_dtype"):
        control["act_dtype"] = jnp.dtype(control["act_dtype"])
    if control.get("cast") and not callable(control["cast"]):
        low = jnp.dtype(control["cast"])
        control["cast"] = lambda a: a.astype(low).astype(a.dtype)
    diffs, states = [], None
    # ONE padded width for the three sequences: the reference's programs
    # are compiled a width
    width = -(-max(len(ids) + len(ents) for ids, ents in rows) // 8) * 8
    for n, (ids, ents) in enumerate(rows):
        seq = ids + [c for c, _, _ in ents]
        padded = jnp.asarray(seq + [0] * (width - len(seq)), jnp.int32)
        # causal, and the convolution looks back: the padding behind the
        # sequence reaches no position of it; row len(ids) - 1 + i
        # predicts generated token i
        at_rows = [len(ids) - 1 + i for i in range(len(ents))]
        if state_tokens is not None and n == len(rows) - 1:
            logp, states = ref.forward_blocked(
                params, padded, hf, positions=at_rows,
                state_tokens=state_tokens, **control)
            states = np.asarray(states)
        else:
            logp = ref.forward_blocked(params, padded, hf,
                                       positions=at_rows, **control)
        for at, (chosen, lp, tops) in zip(np.asarray(logp), ents):
            diffs.append(abs(lp - float(at[chosen])))
            diffs += [abs(v - float(at[t])) for t, v in tops]
    return diffs, states


def readings(diffs: list) -> dict:
    """What is compared (median, p90) and what is only reported."""
    return {"median": statistics.median(diffs),
            "p90": statistics.quantiles(diffs, n=10)[-1],
            "largest": max(diffs), "values": len(diffs)}


def problems(got: dict) -> list:
    """THE comparison: a reading of `measure` against LIMITS, STATE_LIMITS
    and MIN_STEPS, as strings; empty when it passes."""
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
        if key in got and got[key] < least:
            bad.append(f"the compared span held {got[key]:.0f} {key}, "
                       f"under {least}: its tokens did not ride the "
                       f"timed programs")
    if "state_lead_largest" not in got:
        return bad + ["the served state was not read"]
    largest, middle = STATE_LIMITS[got["dtype"]]
    if not got["state_lead_largest"] < largest:
        bad.append(f"largest over the lead's conv layers of |tail - "
                   f"reference| / |reference| "
                   f"{got['state_lead_largest']:.5f} >= {largest}")
    if not got["state_median"] < middle:
        bad.append(f"median over the conv layers of |tail - reference| / "
                   f"|reference| {got['state_median']:.5f} >= {middle}")
    return bad


def served_path(ctx) -> str:
    """Where a run leaves what it served (ids and log-probabilities): in
    its output directory, which holds model/<name>/."""
    return os.path.join(
        os.path.dirname(os.path.dirname(ctx.served.model_dir)),
        "reference_logits_lfm2.served.json")


async def serve(ctx) -> dict:
    """What a reading compares with the reference: `rows` (`served_rows`,
    also left at `served_path`), `span`, `state` (the last row's slot,
    `served_state`) and `state_tokens`, the fewest tokens that slot was
    fed: the prompt and every generated token but the last."""
    rows, span, slot = await served_rows(ctx)
    with open(served_path(ctx), "w") as f:
        json.dump(rows, f)
    ids, ents = rows[-1]
    return {"rows": rows, "span": span,
            "state_tokens": len(ids) + len(ents) - 1,
            "state": await served_state(ctx, slot)}


async def measure(ctx, keep: list = None, served: dict = None,
                  **control) -> dict:
    """Serve, run the reference, return the readings; `keep` (a list) is
    extended with the differences themselves. `control`: an entry of
    CONTROLS, which changes the REFERENCE alone. `served`: what an earlier
    `serve` returned (a control then costs one reference pass and no
    serving)."""
    with open(os.path.join(ctx.served.model_dir, "config.json")) as f:
        hf = json.load(f)
    ref = _load("bench_reference_lfm2", "reference", "lfm2.py")
    t0 = time.monotonic()
    if served is None:
        served = await serve(ctx)
    elif isinstance(served, list):
        served = {"rows": served}
    t1 = time.monotonic()
    engine = ctx.served.worker.engine
    diffs, states = await asyncio.get_running_loop().run_in_executor(
        None, lambda: differences(
            served["rows"], engine.params, hf, ref,
            state_tokens=served.get("state_tokens"), **control))
    if keep is not None:
        keep.extend(diffs)
    got = {**readings(diffs), **served.get("span", {})}
    if states is not None:
        got.update(nearest_state(served["state"], states,
                                 served["state_tokens"],
                                 lead_conv_layers(hf)))
    return {**got, "dtype": engine.model_cfg.dtype,
            "served_s": t1 - t0, "reference_s": time.monotonic() - t1}


async def run(ctx) -> list:
    """Problems found, as strings; empty when the check passes."""
    try:
        got = await measure(ctx)
    except RuntimeError as e:
        return [str(e)]
    print(f"[bench] reference_logits_lfm2: {json.dumps(got)}",
          flush=True, file=sys.stderr)
    return problems(got)
