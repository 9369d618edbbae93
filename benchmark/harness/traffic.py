"""One general traffic generator: (mix file, cell file, seed, seconds) ->
the list of requests a run sends. A pure function; imports no JAX.

A mix is a data file `traffic/<name>.json`:

  kind            "closed" (N clients, each sends its next request when the
                  last one completes) or "open" (arrivals on a schedule)
  prompt_tokens   {"dist": "uniform"|"lognormal", ...} TOTAL prompt tokens
                  as the engine counts them (template included)
  max_tokens      same; every request runs to exactly max_tokens
                  (`ext.ignore_eos`)
  sampling        [{"weight", "temperature", "top_p"}, ...]
  arrivals        open mixes: "poisson", or "onoff" (bursts: Poisson
                  inside `burst.on_s` seconds out of every
                  `burst.on_s + burst.off_s`, the same mean rate)
  pool            closed mixes: how many (prompt, max_tokens) pairs the
                  clients draw from, in order
  lead_in_s       open mixes, optional: the arrivals start this many seconds
                  BEFORE the window, at the same rate, so that the window
                  opens on a system in its steady state and not on an idle
                  one; that stretch is set-up (what it compiles is not the
                  window's) and its requests have a negative `due`
  order           "seed" (the default): `--seed` puts the mix's sizes and
                  gaps in another order; "fixed": every seed sends them in
                  the mix's own order
  shared_prefix   optional {"groups": g, "tokens": n}: request i starts
                  with the n words of group i mod g, its own words after
  admission_pages optional: the one page-count bucket every request's
                  prompt + max_tokens falls in. A mix that names it is
                  checked against the live engine's ladder (the engine
                  compiles a program per admission width; a mix that pins
                  it lets set-up enumerate the rest, see PERF.md); a mix
                  that does not is taken as it is

The cell file `cells/<cell>.json` adds what belongs to the pairing of a
configuration and a mix: `rate_per_s` (open) or `clients` (closed).

Seeds. The SET of sizes and the SET of inter-arrival gaps are drawn from
the mix's own `set_seed`, so every run of a cell offers the same amount of
work; `--seed` puts both in another order (unless the mix says `"order":
"fixed"`) and draws the prompt words and the sampling seeds. Sizes and
arrivals drawn afresh from `--seed` would change the work itself: 51
requests of 64-128 tokens differ by some 3 % in their sum from draw to draw.
Both mixes that have cells today are `"fixed"`, each for a measured reason
(PERF.md, PR 23): a window of `decode-closed` consumes ~45 of its pool, so
another order is another subset (tokens/s moved 10 % between seeds against
1-3 % between two runs of one seed); `chat-open` holds 51 requests a window,
and their order alone moved tokens/s by 13 % and `itl_p95_ms` by 2x.
"""
from __future__ import annotations

import json
import math
import os
import random

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DIST_KINDS = ("uniform", "lognormal", "fixed")
MIX_KINDS = ("closed", "open")


def load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise FileNotFoundError(f"missing benchmark file: {path}")
    with open(path) as f:
        return json.load(f)


def load_mix(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    mix = load_json(path)
    if mix.get("kind") not in MIX_KINDS:
        raise ValueError(f"{path}: unknown generator kind "
                         f"{mix.get('kind')!r} (known: {MIX_KINDS})")
    for key in ("prompt_tokens", "max_tokens"):
        if mix[key].get("dist") not in DIST_KINDS:
            raise ValueError(f"{path}: {key}: unknown dist "
                             f"{mix[key].get('dist')!r} (known: {DIST_KINDS})")
    return mix


def load_cell(name: str, root: str = HERE) -> dict:
    return load_json(os.path.join(root, "cells", f"{name}.json"))


def draw(spec: dict, rng: random.Random) -> int:
    """One whole number from a length distribution, clipped to lo..hi."""
    kind = spec["dist"]
    if kind == "fixed":
        return int(spec["value"])
    lo, hi = int(spec["lo"]), int(spec["hi"])
    if kind == "uniform":
        return rng.randint(lo, hi)
    x = rng.lognormvariate(math.log(spec["median"]), spec["sigma"])
    return max(lo, min(hi, int(round(x))))


def bounds(spec: dict) -> tuple:
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    return int(spec["lo"]), int(spec["hi"])


def check_admission(mix: dict, page_size: int, page_buckets: list) -> int:
    """The widest page bucket a request of the mix is admitted at. A mix
    that names `admission_pages` says all of its requests share that one
    bucket, and this raises if the live ladder says otherwise."""
    (plo, phi), (olo, ohi) = bounds(mix["prompt_tokens"]), \
        bounds(mix["max_tokens"])

    def bucket(tokens):
        pages = -(-tokens // page_size)
        return next(b for b in page_buckets if b >= pages)
    lo, hi = bucket(plo + olo), bucket(phi + ohi)
    if "admission_pages" not in mix:
        return hi
    if lo != hi or hi != mix["admission_pages"]:
        raise ValueError(
            f"mix {mix['name']}: prompt+max_tokens spans page buckets "
            f"{lo}..{hi}, the mix says {mix['admission_pages']}")
    return hi


def size_set(mix: dict, n: int) -> list:
    """The n (prompt_tokens, max_tokens, sampling) triples every seed gets."""
    rng = random.Random(int(mix.get("set_seed", 0)) * 7919 + n)
    weights = [s["weight"] for s in mix["sampling"]]
    out = []
    for _ in range(n):
        samp = rng.choices(mix["sampling"], weights)[0]
        out.append((draw(mix["prompt_tokens"], rng),
                    draw(mix["max_tokens"], rng),
                    {"temperature": samp["temperature"],
                     "top_p": samp["top_p"]}))
    return out


ARRIVAL_KINDS = ("poisson", "onoff")


def gap_set(mix: dict, n: int, seconds: float) -> list:
    """n inter-arrival gaps that sum to `seconds` (or a little under), so
    that every run offers exactly n requests in the window. "poisson":
    exponential gaps. "onoff": the same, on a clock that runs only through
    the first `burst.on_s` of every `burst.on_s + burst.off_s` seconds, so
    the arrivals come in bursts at the same mean rate; in another order
    (`order`) the short and the long gaps still make bursts, of other
    lengths."""
    kind = mix.get("arrivals", "poisson")
    if kind not in ARRIVAL_KINDS:
        raise ValueError(f"mix {mix['name']}: unknown arrivals "
                         f"{kind!r} (known: {ARRIVAL_KINDS})")
    rng = random.Random(int(mix.get("set_seed", 0)) * 104729 + n)
    gaps = [rng.expovariate(1.0) for _ in range(n)]
    if kind == "onoff":
        on, off = float(mix["burst"]["on_s"]), float(mix["burst"]["off_s"])
        busy = seconds * on / (on + off)        # seconds of "on" clock
        scale, t_on, t_prev, out = busy / sum(gaps), 0.0, 0.0, []
        for g in gaps:
            t_on += g * scale
            t = t_on + off * int(t_on // on)    # the off spells passed
            out.append(t - t_prev)
            t_prev = t
        return out
    scale = seconds / sum(gaps)
    return [g * scale for g in gaps]


def in_order(items: list, mix: dict, seed: int, salt: int) -> list:
    """The mix's items in the order `--seed` gives them ("order": "seed",
    the default) or in their own ("fixed")."""
    kind = mix.get("order", "seed")
    if kind not in ("seed", "fixed"):
        raise ValueError(f"mix {mix['name']}: unknown order {kind!r} "
                         f"(known: seed, fixed)")
    items = list(items)
    if kind == "seed":
        random.Random(seed * 1000003 + salt).shuffle(items)
    return items


def prompt_words(rng: random.Random, n: int, vocab: int) -> str:
    """n words of the synthetic vocabulary (ids 5..vocab-1: 0-4 are unk,
    bos, eos and the two template words)."""
    return " ".join(f"w{rng.randrange(5, vocab)}" for _ in range(n))


def schedule(mix: dict, cell: dict, seed: int, seconds: float,
             template_tokens: int, vocab: int) -> dict:
    """What one run sends. Open: {"kind", "lead_in_s", "requests": [{due,
    prompt, max_tokens, sampling, seed}]}, dues relative to the window
    start. The window's requests are always the same set of sizes on the
    same set of gaps; a lead-in is a second, smaller set of both, due
    before 0. Closed: {"kind", "clients", "pool": [...]} taken in order by
    whichever client is free."""
    rng = random.Random(seed)
    share = mix.get("shared_prefix")
    prefixes = {}

    def requests(n: int, salt: int) -> list:
        groups = [i % int(share["groups"]) for i in range(n)] if share \
            else [None] * n
        out = []
        for (p, o, samp), group in in_order(
                list(zip(size_set(mix, n), groups)), mix, seed, salt):
            words, content = p - template_tokens, ""
            if group is not None:
                if group not in prefixes:
                    prefixes[group] = prompt_words(
                        rng, int(share["tokens"]), vocab)
                if words <= int(share["tokens"]):
                    raise ValueError(
                        f"mix {mix['name']}: a prompt of {p} tokens has no "
                        f"room for a shared prefix of {share['tokens']}")
                content = prefixes[group] + " "
                words -= int(share["tokens"])
            out.append({
                "prompt_tokens": p, "max_tokens": o, "sampling": samp,
                "seed": rng.randrange(1 << 31),
                "content": content + prompt_words(rng, words, vocab)})
        return out

    if mix["kind"] == "closed":
        return {"kind": "closed", "clients": int(cell["clients"]),
                "pool": requests(int(mix.get("pool", 1024)), 1)}
    lead = float(mix.get("lead_in_s", 0.0))
    reqs = []
    for span, start, salt in ((seconds, 0.0, 1), (lead, -lead, 3)):
        n = int(round(cell["rate_per_s"] * span))
        if span == seconds:
            n = max(1, n)
        if n == 0:
            continue
        part = requests(n, salt)
        gaps = in_order(gap_set(mix, n, span), mix, seed, salt + 1)
        t = start - gaps[0]      # the first arrival opens the stretch
        for r, g in zip(part, gaps):
            t += g
            r["due"] = t
        reqs += part
    reqs.sort(key=lambda r: r["due"])
    return {"kind": "open", "lead_in_s": lead, "requests": reqs}
