"""Set-up check: Falcon-H1's served path against its plain reference, on the
chip, at the published widths and under the cell's own traffic shape, on
log-probabilities and not on sampled tokens.

Four seeded prompts of 40, 136, 200 and 248 tokens (one below the band of
the cell's prompts, three inside it) are sent greedy through the socket
with `logprobs` and the most `top_logprobs` the frontend gives (8), 16
tokens each, one after the other and each BESIDE FORTY ROWS THAT ARE
DECODING (`HOLDERS`: greedy requests that outlast the four; the first is
520 tokens long, so the longest live row is past 512 tokens and the decode
windows read the 12-page base the timed window's do), so that every step
that carries a compared token is a step of the timed window's own shape: the
first token comes from prefill through `[64, 64]` and `[64, 16]` mixed steps
(the state-space mixer's chunk form for the prompt row and its slot form in
place for the forty decode rows in ONE step, beside attention over the pages
the same layers hold), the rest from 64-row decode windows through the state
slots and the K / V pages. The reference (`benchmark/reference/
falcon_h1.py`: float32, `highest` matmul precision, the per-token
recurrence, attention over the whole sequence) then runs one full forward
pass a prompt over prompt + generated tokens from the engine's own weight
arrays, the MLP and the head in column blocks, and applies the head and the
log-softmax at the 16 compared positions only. Every served value at the
served ids is compared: 4 x 16 x (8 + 1) = 576 numbers.

Three readings of the 576 |differences|: the median and the 90th
percentile, which are held to limits, and the largest, which is printed
beside them and held to none (LIMITS below).

And the STATE ITSELF. The log-probabilities cannot tell a bfloat16 state
from the float32 one that `meta.json` states (LIMIT_READINGS), so the check
also learns which state slot held the last compared sequence (the compared
requests come one after the other, each takes the slot the one before gave
back, and nothing clears a slot at its release: the last one's is still as
its sequence left it when all have ended), waits until the engine has
nothing left to do, reads that slot's `ssm_s` out of the engine's cache and
holds every block's and head's [128, 256] matrix to the one the reference's
per-token recurrence has reached after the same tokens: relative Frobenius
distance, 6 x 32 numbers, two of them limited (STATE_LIMITS below). A
failure of any limit makes the run not `correct`.

Only where the configuration's `meta.json` has a `reference_check` key
whose module is "falcon_h1". `checks/reference_logits.py` is loaded by path
for what the checks share (`served_rows`, `token_id`), with its prompt
lengths replaced on this private copy of the module.
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

PROMPTS = (40, 136, 200, 248)
SEED = None     # None: the shared check's own draw of the prompts
# rows that decode beside every compared prompt: with the prompt's own row
# a step of 41, in the 64-row bucket the timed window's steps have;
# (prompt tokens, max_tokens). The first is the long one (the warm-up
# walk's own first holder): 768 tokens, the cell's 12-page admission
# width, and past 512 from its first token on, so every window of the
# check reads the 12-page base. It outlasts the others (200 tokens, which
# is twice what the ramp and the four compared requests take), so no
# window runs at a narrower width when they end
HOLDERS = 40
FIRST_HOLDER = (520, 248)
HOLDER_TOKENS = (136, 200)

# the controls: each changes the REFERENCE alone (tools/
# olmoe_reference_probe.py --then-controls reads them over what one run
# served); `measure`'s keyword arguments, by name. LIMIT_READINGS below
# says which of them the limits see and which they cannot
CONTROLS = {
    "ref_bf16_state": dict(state_dtype="bfloat16"),
    "ref_no_ssm": dict(without_ssm=True),
    "ref_bf16_act": dict(act_dtype="bfloat16"),
    "ref_float8": dict(cast="float8_e4m3fn"),
}

# (90th percentile, median) of |served - reference| over the 576
# log-probabilities. The weights are the same bfloat16 values on both
# sides; the served path rounds every activation, the stored K / V rows,
# the convolution's tail and each projection's output to bfloat16 and keeps
# the mixer's state, its arithmetic and the head's sums in float32. No
# router stands in this model, so nothing flips: the differences are
# rounding alone, and the largest is printed beside the two that are limited
# only because a maximum over 576 draws of a rounding error has the widest
# spread of the three. The limits rest on the ONE draw this check runs (its
# prompts come from the shared check's fixed SEED, its weights from the
# engine's fixed seed, its requests are greedy and sent in a fixed order
# beside rows that outlast them: `--seed` moves nothing here; the same
# tree read the same digits in two processes).
# LIMIT_READINGS, TPU v5e, the builder's chip run of PR 45 (call 8, the
# review round: one served process, the controls over what it served), a
# reading = ((p90, median, largest) of the log-probabilities, (p90 over
# the FIRST block's 32 heads, largest over all 6 x 32) of the state's
# distances); benchmark/tests/test_falcon_h1_cell.py holds the limits to
# them:
#   "change": the check as a run makes it: 0.0263 / 0.0114; the state
#     0.0042 / 0.0223. (At 3 decode steps, another window program, the same
#     tree read 0.0271 / 0.0111: what a change of the programs' shapes
#     moves.)
#   "ref_float8": the REFERENCE with its weights rounded to float8 (e4m3),
#     the nearest precision below the configuration's matmuls: 0.452 /
#     0.192, 17 x the change; the state 0.184 / 0.457.
#   "ref_no_ssm": the REFERENCE with the state-space branch left out of
#     every block: 5.45 / 4.08, nats. (Its first block's state is the
#     change's: that block's mixer reads the embedding alone.)
#   "ref_bf16_state": the REFERENCE with its state rounded to bfloat16
#     after every token, the nearest precision below `assumed.state`: the
#     log-probabilities read 0.0275 / 0.0115 (+4 % / +1 % on the change: a
#     state rounded to 8 bits of mantissa is a random walk of 2**-9 steps
#     over a head's memory of 3..300 tokens, and what it adds to a
#     log-probability lies under what the served path's own bfloat16
#     activations add, the whole of the change's reading), and no limit on
#     them can see it. The STATE does: 0.0146 over the first block, 3.5 x
#     the change; every one of that block's 32 heads reads further (0.0049
#     at the least) than the change's furthest (0.0046).
# So: LIMITS, p90 0.06 and median 0.025, each 2.2 x the change, the float8
# reference 7.5 x past both; STATE_LIMITS, 0.008 on the 90th percentile of
# the first block's heads, 1.9 x the change and the bfloat16 state 1.8 x
# past it, and 0.07 on the largest of all 192, 3.1 x the change and the
# float8 reference 6.5 x past it (its SMALLEST is 0.056).
# Why the first block, and why 0.004 and not float32's 1e-6: the state's
# INPUTS (x, B, dt) are bfloat16 activations on the served side alone, three
# or four roundings of 2**-9 in the first block (0.0020..0.0046 a head) and
# the stream's compounded error by the sixth (0.007..0.022), where a
# bfloat16 state's own 0.005..0.017 no longer stands out (0.0126..0.0227).
# In float32 (the tiny rehearsal on the CPU) the change reads 5.9e-7 at the
# largest and the control 0.0075 at the median.
# CONTROLS_NOT_SEEN: the one control that ISSUE 45 asked to fail and that
# does not, by construction: the reference with bfloat16 ACTIVATIONS at the
# block's joints is the served path's own precision (0.0332 / 0.0121 on
# the log-probabilities; its first block's state is NEARER the served one,
# 0.0032). PERF.md section 6, PR 45.
LIMIT_READINGS = {
    "change": ((0.026334762573242188, 0.011434555053710938,
                0.05035686492919922),
               (0.004177467603019073, 0.02233528050326315)),
    "ref_float8": ((0.4518589019775391, 0.19194412231445312,
                    0.8462343215942383),
                   (0.18357599756849832, 0.45715145916308086)),
    "ref_no_ssm": ((5.453596496582032, 4.084693431854248,
                    7.581581115722656),
                   (0.004177467603019073, 1.4837610784340232)),
    "ref_bf16_state": ((0.02751502990722656, 0.011513233184814453,
                        0.048951148986816406),
                       (0.014564601695607873, 0.022701717008069142)),
}
CONTROLS_NOT_SEEN = {
    "ref_bf16_act": ((0.03322763442993164, 0.012137889862060547,
                      0.05647563934326172),
                     (0.0031571170103502203, 0.024156216343963647)),
}
LIMITS = {"bfloat16": (0.06, 0.025), "float32": (2e-3, 5e-4)}
# (90th percentile over the first block's heads, largest over every block
# and head) of the state's relative distance from the reference's
STATE_LIMITS = {"bfloat16": (0.008, 0.07), "float32": (1e-4, 1e-4)}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def applies(config_meta: dict) -> bool:
    return (config_meta.get("reference_check") or {}).get("module") \
        == "falcon_h1"


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shared():
    """checks/reference_logits.py, a private copy with this check's
    prompt lengths."""
    mod = _load("bench_check_reference_logits_for_falcon_h1", "checks",
                "reference_logits.py")
    mod.PROMPTS = PROMPTS
    if SEED is not None:
        mod.SEED = SEED
    return mod


def live_state_slots(engine) -> set:
    """The state slots of the sequences that hold a decode slot now."""
    return {seq.state_slot for seq in list(engine.scheduler.running)
            if seq is not None and seq.state_slot >= 0}


class SlotSeen:
    """`ctx` for the shared check's `served_rows`, whose requests also say
    which state slot held each: at a request's first token, the one live
    slot that no holder has (`held`: the holders', all live)."""

    def __init__(self, ctx, held: set):
        self.ctx, self.held, self.slots = ctx, held, []

    def __getattr__(self, name):
        return getattr(self.ctx, name)

    async def request(self, prompt_tokens, max_tokens, seed, sampling,
                      extra=None):
        # CheckCtx.request's own request, and an event at its first token
        from harness import loadgen, traffic
        req = {"prompt_tokens": prompt_tokens, "max_tokens": max_tokens,
               "seed": seed, "sampling": sampling, "extra": extra or {},
               "content": traffic.prompt_words(
                   random.Random(seed),
                   prompt_tokens - (self.ctx.template_tokens or 0),
                   self.ctx.vocab)}
        streams = asyncio.Event()
        task = asyncio.create_task(loadgen.do_request(
            self.ctx.served.port, self.ctx.model, req,
            loadgen.Row(logprobs=[]), streams))
        await streams.wait()
        self.slots.append(
            live_state_slots(self.ctx.served.worker.engine) - self.held)
        return await task


async def served_rows(ctx) -> tuple:
    """The shared check's requests (its `served_rows`), sent while HOLDERS
    greedy rows decode: the long holder first, the others together once it
    streams (they join it three a mixed step), the compared prompts when
    all stream. A holder that ended before the last compared request did
    is an error: the rows beside it were fewer than the check says.
    -> (the rows, the state slot that held the LAST of them: the compared
    requests come one after the other and each takes the slot the one
    before it gave back, so the last one's is the slot that still holds a
    compared sequence's state when all have ended)."""
    from harness import loadgen, traffic
    base = shared()
    engine = ctx.served.worker.engine

    def holder(i):
        n_prompt, n_out = HOLDER_TOKENS if i else FIRST_HOLDER
        seed = 9100 + i
        req = {"prompt_tokens": n_prompt, "max_tokens": n_out, "seed": seed,
               "sampling": {"temperature": 0.0},
               "extra": {"logprobs": True, "top_logprobs": base.TOP},
               "content": traffic.prompt_words(
                   random.Random(seed), n_prompt - ctx.template_tokens,
                   ctx.vocab)}
        streams = asyncio.Event()
        return streams, n_out, asyncio.create_task(loadgen.do_request(
            ctx.served.port, ctx.model, req, loadgen.Row(), streams))

    holders = [holder(0)]
    await holders[0][0].wait()
    holders += [holder(i) for i in range(1, HOLDERS)]
    try:
        for streams, _, _ in holders:
            await streams.wait()
        seen = SlotSeen(ctx, live_state_slots(engine))
        out = await base.served_rows(seen)
        done = time.monotonic()
    finally:
        rows = await asyncio.gather(*(task for _, _, task in holders))
    for row, (_, n_out, _) in zip(rows, holders):
        if row.get("status") != 200 or row.get("error") \
                or len(row["frames"]) != n_out:
            raise RuntimeError(
                f"a holder failed: {row.get('status')} {row.get('error')} "
                f"{len(row['frames'])} of {n_out} tokens")
        if row["end"] <= done:
            raise RuntimeError("a holder ended before the compared "
                               "requests did: fewer rows beside them")
    if len(seen.held) != HOLDERS or any(len(s) != 1 for s in seen.slots):
        raise RuntimeError(
            f"state slots: {len(seen.held)} holders' and, at each compared "
            f"request's first token, {seen.slots} beside them")
    return out, seen.slots[-1].pop()


async def served_state(engine, slot: int):
    """Every block's state matrix in `slot`, [L, H, P, N] float32 on the
    host, read once the engine has nothing left to do (its programs
    donate the cache: a read beside a step would race it)."""
    import numpy as np
    while engine.has_work():
        await asyncio.sleep(0.01)
    return np.asarray(engine.cache["ssm_s"][:, slot], np.float32)


def state_readings(distances: list) -> dict:
    """Of `state_distances`' [L][H]: what is compared (the 90th percentile
    over the first block's heads, the largest of all) and what is only
    reported (each block's median)."""
    return {"state_first_p90": statistics.quantiles(distances[0], n=10)[-1],
            "state_largest": max(max(layer) for layer in distances),
            "state_by_block": [statistics.median(layer)
                               for layer in distances]}


def state_problems(got: dict) -> list:
    """A reading's state against STATE_LIMITS, as strings."""
    if "state_first_p90" not in got:
        return ["the served state was not read"]
    first, largest = STATE_LIMITS[got["dtype"]]
    bad = []
    if not got["state_first_p90"] < first:
        bad.append(f"90th percentile over the first block's heads of "
                   f"|state - reference| / |reference| "
                   f"{got['state_first_p90']:.5f} >= {first}")
    if not got["state_largest"] < largest:
        bad.append(f"largest |state - reference| / |reference| "
                   f"{got['state_largest']:.4f} >= {largest}")
    return bad


def differences(rows: list, params, hf: dict, ref, state_tokens=None,
                **control) -> tuple:
    """(|served - reference| for every served log-probability, the
    reference's states): the reference's head is applied at the compared
    rows only. `state_tokens`: a count of the LAST row's tokens; with it
    the second value is every block's state after that many and after one
    more, [2, L, H, P, N], else None. `control`:
    `forward_blocked`'s own keyword arguments, dtypes and the cast by
    name."""
    import jax.numpy as jnp
    import numpy as np
    for key in ("state_dtype", "act_dtype"):
        if key in control:
            control[key] = jnp.dtype(control[key])
    if control.get("cast"):
        low = jnp.dtype(control["cast"])
        control["cast"] = lambda a: a.astype(low).astype(a.dtype)
    diffs, states = [], None
    for n, (ids, ents) in enumerate(rows):
        seq = ids + [c for c, _, _ in ents]
        width = -(-len(seq) // 8) * 8
        padded = jnp.asarray(seq + [0] * (width - len(seq)), jnp.int32)
        # causal, and the recurrence runs forward: the padding behind the
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


def state_distances(served, reference) -> list:
    """|served - reference| / |reference| (Frobenius, float64) of every
    head's [P, N] state matrix: [L][H]."""
    import numpy as np
    served, reference = (np.asarray(a, np.float64)
                         for a in (served, reference))
    norm = lambda a: np.sqrt((a * a).sum(axis=(-2, -1)))  # noqa: E731
    return (norm(served - reference) / norm(reference)).tolist()


def nearest_state(served, reference, tokens: int) -> dict:
    """`state_readings` of the served slot against the reference's state
    after `tokens` tokens or after one more (`reference`: both, [2, L, H,
    P, N]), whichever lies nearer, and `state_fed`, which that was. The
    two lie a token's whole input apart (0.1 to 1 of a fast head's
    state, hundreds of times any limit here), so the choice can hide no
    fault."""
    both = [state_distances(served, ref) for ref in reference]
    mean = [statistics.fmean(d for layer in dist for d in layer)
            for dist in both]
    one_more = mean[1] < mean[0]
    return {**state_readings(both[one_more]),
            "state_fed": tokens + one_more}


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
    return bad + state_problems(got)


def served_path(ctx) -> str:
    """Where a run leaves what it served (ids and log-probabilities): in
    its output directory, which holds model/<name>/."""
    return os.path.join(
        os.path.dirname(os.path.dirname(ctx.served.model_dir)),
        "reference_logits_falcon_h1.served.json")


async def serve(ctx) -> dict:
    """What a reading compares with the reference: `rows` (`served_rows`,
    also left at `served_path`), `state` (the last row's slot,
    `served_state`) and `state_tokens`, the fewest tokens that slot was
    fed: the prompt and every generated token but the last. The last was
    sampled and has nothing to predict, but a decode window that emitted
    it before its last step feeds it all the same (its K / V row has a
    page; what that step samples is dropped), so the slot holds the state
    after this many tokens or after one more, as the windows' edges fell:
    `nearest_state` tells which."""
    rows, slot = await served_rows(ctx)
    with open(served_path(ctx), "w") as f:
        json.dump(rows, f)
    ids, ents = rows[-1]
    return {"rows": rows, "state_tokens": len(ids) + len(ents) - 1,
            "state": await served_state(ctx.served.worker.engine, slot)}


async def measure(ctx, keep: list = None, served: dict = None,
                  **control) -> dict:
    """Serve, run the reference, return the readings; `keep` (a list) is
    extended with the 576 differences themselves. `control`: an entry of
    CONTROLS, which changes the REFERENCE alone. `served`: what an earlier
    `serve` returned (a control then costs one reference pass and no
    serving), or its rows alone, as `served_path` keeps them (no state is
    read then, and `problems` says so)."""
    with open(os.path.join(ctx.served.model_dir, "config.json")) as f:
        hf = json.load(f)
    ref = _load("bench_reference_falcon_h1", "reference", "falcon_h1.py")
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
    got = readings(diffs)
    if states is not None:
        got.update(nearest_state(served["state"], states,
                                 served["state_tokens"]))
    return {**got, "dtype": engine.model_cfg.dtype,
            "served_s": t1 - t0, "reference_s": time.monotonic() - t1}


async def run(ctx) -> list:
    """Problems found, as strings; empty when the check passes."""
    try:
        got = await measure(ctx)
    except RuntimeError as e:
        return [str(e)]
    print(f"[bench] reference_logits_falcon_h1: {json.dumps(got)}",
          flush=True, file=sys.stderr)
    return problems(got)
