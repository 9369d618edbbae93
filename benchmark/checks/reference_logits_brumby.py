"""Set-up check: Brumby's served path against its plain reference, on the
chip, at the published widths and under the cell's own traffic shape, on
log-probabilities and on the state itself.

Four seeded prompts of 40, 136, 200 and 248 tokens (one below the band of
the cell's prompts, three inside it) are sent greedy through the socket
with `logprobs` and the most `top_logprobs` the frontend gives (8), 128
tokens each, BESIDE TWELVE ROWS THAT ARE DECODING (`HOLDERS`: greedy
requests that outlast the four; the first is 520 tokens long, so the
engine's page bookkeeping names the 12-page programs the timed window's
steps have) and beside one another: each is sent when the one before it
streams, so that the four prefill one a mixed step (`--max-prefill-batch
1`) and then decode together, sixteen rows a step, which is the cell's own
batch. Every step that carries a compared token is a step of the timed
window's own shape: the first token comes from prefill through `[16, 64]`
and `[16, 256]` mixed steps (the chunk form for the prompt row and the
slot-addressed kernel for the decode rows in ONE step, both writing the
one state leaf), the rest from 16-row decode windows through the kernel
alone. (Sent one after the other, 4 x 128 tokens would outlast every
holder that fits the 12-page admission bucket.) The reference
(`benchmark/reference/brumby.py`: float32, `highest` matmul precision, the
masked quadratic form over the whole sequence, no state) then runs one
full forward pass a prompt over prompt + generated tokens from the
engine's own weight arrays, the MLP and the head in column blocks, and
applies the head and the log-softmax at the 128 compared positions only.
Every served value at the served ids is compared: 4 x 128 x (8 + 1) = 4608
numbers. Three readings of them: the median and the 90th percentile, which
are held to limits, and the largest, which is printed beside them and held
to none (LIMITS below).

And the STATE ITSELF, which is all this model keeps of a context. The
check learns which state slot holds each compared sequence (at its first
token, the one live slot that no earlier request has), waits until the
engine has nothing left to do (nothing clears a slot at its release: it is
still as its sequence left it), reads the LAST compared sequence's slot of
`ret_s` and `ret_z` out of the engine's cache and holds the FIRST layer's
matrix and normaliser (all 8 key-value heads) to what the reference's
per-token recurrence has reached after the same tokens: for each head, the
largest |difference| over the head's largest |entry|, 2 x 8 numbers, the
largest of each leaf limited (STATE_LIMITS below). The later layers'
inputs carry the served path's own activation rounding (PERF.md section 7,
PR 45) and are not compared: the first layer is the one whose inputs are
the embedding's rows on both sides. A failure of any limit makes the run
not `correct`.

Only where the configuration's `meta.json` has a `reference_check` key
whose module is "brumby". `checks/reference_logits.py` is loaded by path
for what the checks share (`token_id`, `TOP`, `SEED`).
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
N_TOKENS = 128
# rows that decode beside the compared prompts: with the four a step of
# sixteen, the cell's batch; (prompt tokens, max_tokens). The first is the
# long one (the warm-up walk's own first holder): 768 tokens, the cell's
# 12-page admission width, and past 512 from its first token on. Every
# holder outlasts the four (16 mixed steps to admit all, then 128 steps)
HOLDERS = 12
FIRST_HOLDER = (520, 248)
HOLDER_TOKENS = (136, 224)

# the controls: each changes the REFERENCE alone (tools/
# olmoe_reference_probe.py --then-controls reads them over what one run
# served); `measure`'s keyword arguments, by name. LIMIT_READINGS below
# says which of them the limits see and which they cannot
CONTROLS = {
    "ref_float8": dict(cast="float8_e4m3fn"),
    "ref_gate_one": dict(gate_one=True),
    "ref_degree_one": dict(degree=1),
    "ref_lost_state": dict(reset_every=16),
    "ref_bf16_state": dict(state_dtype="bfloat16"),
    "ref_bf16_act": dict(act_dtype="bfloat16"),
}

# (90th percentile, median) of |served - reference| over the 4608
# log-probabilities, and (the matrix S, the normaliser z): over the first
# layer's 8 key-value heads, the largest of max |served - reference| / max
# |reference|. The weights are the same bfloat16 values on both sides; the
# served path rounds every activation and each projection's output to
# bfloat16 (q, k and v among them, which are the state's inputs) and keeps
# the state, phi, every sum over a sequence and the head's sums in
# float32. No router stands in this model, so nothing flips: the
# differences are rounding alone. The limits rest on the ONE draw this
# check runs (its prompts come from the shared check's fixed SEED, its
# weights from the engine's fixed seed, its requests are greedy and sent
# in a fixed order beside rows that outlast them: `--seed` moves nothing
# here; thirteen runs in three processes read the same digits but for the
# last of the median).
# LIMIT_READINGS, TPU v5e, the builder's chip run of PR 55 (call 4: one
# served process, the controls over what it served), a reading = ((p90,
# median, largest) of the log-probabilities, (S, z) of the state):
#   "change": the check as a run makes it: 0.0341 / 0.0137; the state
#     0.0095 / 0.0046.
#   "ref_bf16_state": the REFERENCE with S and z rounded to bfloat16 after
#     every token, the nearest precision below `assumed.state_dtype`:
#     0.236 / 0.0783, 6.9 x and 5.7 x the change; the state 0.0652 /
#     0.0453, 6.8 x and 9.9 x. ALL FOUR limits see it: where the state is
#     the whole context, 8 bits of mantissa on it are a random walk over a
#     head's memory of 10..1000 tokens that reaches the log-probabilities
#     (Falcon-H1's bfloat16 state, beside attention, did not).
#   "ref_float8": the REFERENCE with its weights rounded to float8 (e4m3),
#     the nearest precision below the configuration's matmuls: 1.05 /
#     0.475, 31 x and 35 x the change; the state 0.215 / 0.265.
#   "ref_gate_one": the gate set to 1: 4.18 / 2.78 nats; the state 1.00 /
#     0.98 (a state that never forgets is another state).
#   "ref_degree_one": |q . k| for its square: 1.97 / 1.09 nats; the state
#     is the change's (the control changes the weights a query gives, not
#     what the keys leave).
#   "ref_lost_state": the state zeroed at every 16-token edge: 5.31 /
#     3.99 nats; the state 2.07 / 14.6.
# So: LIMITS, p90 0.09 and median 0.033, and STATE_LIMITS, 0.025 on S and
# 0.015 on z: each the geometric mean of the change and the bfloat16
# state, 2.4-3.3 x the change with the bfloat16 state 2.4-3.0 x past it
# and the float8 reference 8.6-18 x past. In float32 (the tiny rehearsal
# on the CPU) the change reads 1.9e-6 / 9.5e-7 and 1.6e-7 / 1.7e-7, and
# every control, bfloat16 activations among them, fails all four.
# CONTROLS_NOT_SEEN: the one control that is expected to pass: the
# reference with bfloat16 ACTIVATIONS at the block's joints is the served
# path's own precision (0.0314 / 0.0156 on the log-probabilities, NEARER
# the served values than the float32 reference at the 90th percentile;
# the state 0.0098 / 0.0051). PERF.md section 6, PR 55.
LIMIT_READINGS = {
    "change": ((0.034105539321899414, 0.013677120208740234,
                0.07720756530761719),
               (0.009543332102806917, 0.0046014277791857966)),
    "ref_bf16_state": ((0.23584537506103515, 0.0783090591430664,
                0.8909645080566406),
               (0.06522252824571398, 0.04533277239118304)),
    "ref_float8": ((1.0452499389648438, 0.4746890068054199,
                2.1500720977783203),
               (0.21474898355552408, 0.2652417475578327)),
    "ref_gate_one": ((4.181278324127197, 2.784451484680176,
                6.580063819885254),
               (1.0030599728818732, 0.981400312411923)),
    "ref_degree_one": ((1.972116470336914, 1.0928926467895508,
                3.343020439147949),
               (0.009543332102806917, 0.0046014277791857966)),
    "ref_lost_state": ((5.313811588287353, 3.9938483238220215,
                7.832265377044678),
               (2.0704515968551473, 14.630483373856837)),
}
CONTROLS_NOT_SEEN = {
    "ref_bf16_act": ((0.03137969970703125, 0.015594005584716797,
                0.0938568115234375),
               (0.009772276656209434, 0.005103567249301942)),
}
LIMITS = {"bfloat16": (0.09, 0.033), "float32": (2e-3, 5e-4)}
STATE_LIMITS = {"bfloat16": (0.025, 0.015), "float32": (1e-4, 1e-4)}

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def applies(config_meta: dict) -> bool:
    return (config_meta.get("reference_check") or {}).get("module") \
        == "brumby"


def _load(name: str, *parts: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shared():
    """checks/reference_logits.py, a private copy."""
    return _load("bench_check_reference_logits_for_brumby", "checks",
                 "reference_logits.py")


def live_state_slots(engine) -> set:
    """The state slots of the sequences that hold a decode slot now."""
    return {seq.state_slot for seq in list(engine.scheduler.running)
            if seq is not None and seq.state_slot >= 0}


def _request(ctx, n_prompt: int, n_out: int, seed: int, top: int) -> tuple:
    """One greedy request with log-probabilities, started: (an event set
    at its first token, the task that gives its row, its content)."""
    from harness import loadgen, traffic
    content = traffic.prompt_words(
        random.Random(seed), n_prompt - ctx.template_tokens, ctx.vocab)
    req = {"prompt_tokens": n_prompt, "max_tokens": n_out, "seed": seed,
           "sampling": {"temperature": 0.0},
           "extra": {"logprobs": True, "top_logprobs": top},
           "content": content}
    streams = asyncio.Event()
    return streams, asyncio.create_task(loadgen.do_request(
        ctx.served.port, ctx.model, req, loadgen.Row(logprobs=[]),
        streams)), content


async def served_rows(ctx) -> tuple:
    """[(prompt ids, [(chosen id, logprob, [(id, logprob)] * TOP)] * N)]
    of the compared requests, sent while HOLDERS greedy rows decode: the
    long holder first, the others together once it streams, the compared
    prompts when all stream, each when the one before it streams. A
    holder that ended before the last compared request did is an error:
    the rows beside it were fewer than the check says. -> (the rows, the
    state slot of the LAST of them)."""
    from tokenizers import Tokenizer
    base = shared()
    engine = ctx.served.worker.engine
    tok = Tokenizer.from_file(os.path.join(ctx.served.model_dir,
                                           "tokenizer.json"))
    holders = [_request(ctx, *FIRST_HOLDER, 9100, base.TOP)]
    await holders[0][0].wait()
    holders += [_request(ctx, *HOLDER_TOKENS, 9100 + i, base.TOP)
                for i in range(1, HOLDERS)]
    compared, slots = [], []
    try:
        for streams, _, _ in holders:
            await streams.wait()
        held = live_state_slots(engine)
        for n, n_prompt in enumerate(PROMPTS):
            compared.append(_request(ctx, n_prompt, N_TOKENS, base.SEED + n,
                                     base.TOP))
            await compared[-1][0].wait()
            slots.append(live_state_slots(engine) - held)
            held |= slots[-1]
        rows = await asyncio.gather(*(task for _, task, _ in compared))
        done = time.monotonic()
    finally:
        held_rows = await asyncio.gather(*(task for _, task, _ in holders))
    for i, row in enumerate(held_rows):
        n_out = (HOLDER_TOKENS if i else FIRST_HOLDER)[1]
        if row.get("status") != 200 or row.get("error") \
                or len(row["frames"]) != n_out:
            raise RuntimeError(
                f"a holder failed: {row.get('status')} {row.get('error')} "
                f"{len(row['frames'])} of {n_out} tokens")
        if row["end"] <= done:
            raise RuntimeError("a holder ended before the compared "
                               "requests did: fewer rows beside them")
    if len(held) != HOLDERS + len(PROMPTS) or any(
            len(s) != 1 for s in slots):
        raise RuntimeError(f"state slots: at each compared request's first "
                           f"token, {slots} beside the holders'")
    out = []
    for row, (_, _, content) in zip(rows, compared):
        if row.get("status") != 200 or row.get("error"):
            raise RuntimeError(f"logprobs request failed: "
                               f"{row.get('status')} {row.get('error')}")
        # the words the request drew, through the template it rendered
        ids = tok.encode(f"w3 {content} w4").ids
        if len(ids) != row["usage"]["prompt_tokens"]:
            raise RuntimeError(
                f"{len(ids)} prompt ids reconstructed, the server counted "
                f"{row['usage']['prompt_tokens']}")
        ents = row.get("logprobs") or []
        if len(ents) != N_TOKENS:
            raise RuntimeError(f"{len(ents)} logprob entries for "
                               f"{N_TOKENS} tokens")
        out.append((ids, [
            (base.token_id(e["token"]), float(e["logprob"]),
             [(base.token_id(t["token"]), float(t["logprob"]))
              for t in e["top_logprobs"]]) for e in ents]))
    return out, slots[-1].pop()


async def served_state(engine, slot: int) -> tuple:
    """The first layer's (S [Hkv, hd, F], z [Hkv, F]) in `slot`, float32
    on the host, read once the engine has nothing left to do (its
    programs donate the cache: a read beside a step would race it)."""
    import numpy as np
    while engine.has_work():
        await asyncio.sleep(0.01)
    return tuple(np.asarray(engine.cache[leaf][0, slot], np.float32)
                 for leaf in ("ret_s", "ret_z"))


def state_distances(served, reference) -> list:
    """For each leaf (S, z): every key-value head's max |served -
    reference| / max |reference|, float64: [2][Hkv]."""
    import numpy as np
    out = []
    for got, want in zip(served, reference):
        got, want = (np.asarray(a, np.float64).reshape(a.shape[0], -1)
                     for a in (got, want))
        out.append((np.abs(got - want).max(axis=1)
                    / np.abs(want).max(axis=1)).tolist())
    return out


def nearest_state(served, reference, tokens: int) -> dict:
    """The served slot against the reference's state after `tokens`
    tokens or after one more (`reference`: both, (S [2, ...], z [2,
    ...])), whichever lies nearer, and `state_fed`, which that was. The
    two lie a token's whole input apart (1 / memory of a head's state,
    many times any limit here), so the choice can hide no fault."""
    both = [state_distances(served, [leaf[i] for leaf in reference])
            for i in (0, 1)]
    mean = [statistics.fmean(d for leaf in dist for d in leaf)
            for dist in both]
    one_more = mean[1] < mean[0]
    s, z = both[one_more]
    return {"state_s": max(s), "state_z": max(z),
            "state_s_by_head": s, "state_z_by_head": z,
            "state_fed": tokens + one_more}


def state_problems(got: dict) -> list:
    """A reading's state against STATE_LIMITS, as strings."""
    if "state_s" not in got:
        return ["the served state was not read"]
    bad = []
    for key, what, limit in zip(("state_s", "state_z"),
                                ("matrix", "normaliser"),
                                STATE_LIMITS[got["dtype"]]):
        if not got[key] < limit:
            bad.append(f"the first layer's {what}: largest over its heads "
                       f"of max |state - reference| / max |reference| "
                       f"{got[key]:.5f} >= {limit}")
    return bad


def differences(rows: list, params, hf: dict, ref, state_tokens=None,
                **control) -> tuple:
    """(|served - reference| for every served log-probability, the
    reference's states): the reference's head is applied at the compared
    rows only. `state_tokens`: a count of the LAST row's tokens; with it
    the second value is the first layer's state after that many and after
    one more, else None. `control`: `forward_blocked`'s own keyword
    arguments, dtypes and the cast by name."""
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
        # causal: the padding behind the sequence reaches no position of
        # it; row len(ids) - 1 + i predicts generated token i
        at_rows = [len(ids) - 1 + i for i in range(len(ents))]
        if state_tokens is not None and n == len(rows) - 1:
            logp, states = ref.forward_blocked(
                params, padded, hf, positions=at_rows,
                state_tokens=state_tokens, **control)
            states = tuple(np.asarray(leaf) for leaf in states)
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
    """THE comparison: a reading of `measure` against LIMITS and
    STATE_LIMITS, as strings; empty when it passes."""
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
        "reference_logits_brumby.served.json")


async def serve(ctx) -> dict:
    """What a reading compares with the reference: `rows` (`served_rows`,
    also left at `served_path`), `state` (the last row's slot,
    `served_state`) and `state_tokens`, the fewest tokens that slot was
    fed: the prompt and every generated token but the last. The last was
    sampled and has nothing to predict, but a decode window that emitted
    it before its last step feeds it all the same (what that step samples
    is dropped), so the slot holds the state after this many tokens or
    after one more, as the windows' edges fell: `nearest_state` tells
    which."""
    rows, slot = await served_rows(ctx)
    with open(served_path(ctx), "w") as f:
        json.dump(rows, f)
    ids, ents = rows[-1]
    return {"rows": rows, "state_tokens": len(ids) + len(ents) - 1,
            "state": await served_state(ctx.served.worker.engine, slot)}


async def measure(ctx, keep: list = None, served: dict = None,
                  **control) -> dict:
    """Serve, run the reference, return the readings; `keep` (a list) is
    extended with the 4608 differences themselves. `control`: an entry of
    CONTROLS, which changes the REFERENCE alone. `served`: what an earlier
    `serve` returned (a control then costs one reference pass and no
    serving), or its rows alone, as `served_path` keeps them (no state is
    read then, and `problems` says so)."""
    with open(os.path.join(ctx.served.model_dir, "config.json")) as f:
        hf = json.load(f)
    ref = _load("bench_reference_brumby", "reference", "brumby.py")
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
    print(f"[bench] reference_logits_brumby: {json.dumps(got)}",
          flush=True, file=sys.stderr)
    return problems(got)
