"""Metric arithmetic on the load generator's rows. Pure Python, no JAX.

A row is one request: `due` (open mixes), `send`, `frames` (the arrival
time of every token frame; one token per frame with the synthetic
tokenizer), `end`, `status`, `finish`, `usage`, `prompt_tokens`,
`max_tokens`, `error`. Times are CLOCK_MONOTONIC seconds.
"""
from __future__ import annotations


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def request_ok(row: dict) -> bool:
    """Answered 200, ran to exactly max_tokens with finish_reason
    "length", one frame per token, and the prompt counted as generated."""
    u = row.get("usage") or {}
    return (row.get("status") == 200 and not row.get("error")
            and row.get("finish") == "length"
            and u.get("completion_tokens") == row["max_tokens"]
            and len(row.get("frames", ())) == row["max_tokens"]
            and u.get("prompt_tokens") == row["prompt_tokens"])


def window_rows(rows, t0: float, t1: float, kind: str) -> dict:
    """Split the window's requests. Open: every request due in the window
    is attempted. Closed: a request that ENDED (or failed) in the window is
    attempted; one still streaming at t1 is cut by the benchmark itself and
    counts as neither."""
    attempted, failed = [], []
    for r in rows:
        if kind == "open":
            if r.get("phase") != "window" \
                    or not t0 <= r.get("due", -1.0) < t1 + 1e-6:
                continue
        else:
            if r.get("kind") != "client":
                continue
            done = r.get("end")
            if done is None:
                if not (r.get("error") or (r.get("status") or 200) != 200):
                    continue       # cut at the end of the window
                done = r["send"]
            if not t0 <= done < t1:
                continue
        attempted.append(r)
        if not request_ok(r):
            failed.append(r)
    return {"attempted": attempted, "failed": failed}


def tokens_in_window(rows, t0: float, t1: float) -> int:
    """Token frames delivered inside [t0, t1), whichever request they
    belong to: all the work of the window over all of its time."""
    return sum(1 for r in rows for t in r.get("frames", ())
               if t0 <= t < t1)


def pooled_gaps(rows, t0: float, t1: float) -> list:
    """Every gap between successive token frames of one stream whose later
    frame lies in the window, pooled over streams, in seconds. The engine
    commits a window of steps at a time, so most gaps are near zero and
    one in `decode_steps` is a whole step or several. The long gaps are whole
    numbers of device steps (~105 ms each on a v5e today), so a percentile
    is steady only inside one of those plateaus: the 95th is, the 99th
    flips between three steps and four from run to run (PERF.md, PR 23)."""
    gaps = []
    for r in rows:
        f = r.get("frames", ())
        gaps.extend(b - a for a, b in zip(f, f[1:]) if t0 <= b < t1)
    return gaps


MIN_FRAMES = 16   # two windows of steps: fewer is one commit, not a pace


def tpot_per_request(rows, t0: float, t1: float) -> list:
    """Time per output token of each stream inside the window: (last -
    first frame time) / (frames - 1) over its frames in [t0, t1), for every
    stream with MIN_FRAMES or more there, finished or not, in seconds. All
    the streams and all the time of the window, not only the requests that
    happened to end in it."""
    out = []
    for r in rows:
        f = [t for t in r.get("frames", ()) if t0 <= t < t1]
        if len(f) >= MIN_FRAMES:
            out.append((f[-1] - f[0]) / (len(f) - 1))
    return out


def ttft_from_due(rows, window_s: float) -> list:
    """First-token time from the DUE time of each attempted request; a
    request that failed or streamed nothing counts as the window length."""
    out = []
    for r in rows:
        f = r.get("frames", ())
        out.append(f[0] - r["due"] if f and request_ok(r) else window_s)
    return out


def end_to_end(rows, t0: float, seconds: float, kind: str,
               chips: int) -> dict:
    """The end-to-end metrics of one run, by name."""
    t1 = t0 + seconds
    split = window_rows(rows, t0, t1, kind)
    att = split["attempted"]
    out = {"output_tok_s": tokens_in_window(rows, t0, t1) / seconds / chips}
    gaps = pooled_gaps(rows, t0, t1)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    tp = tpot_per_request(rows, t0, t1)
    if tp:
        out["tpot_p50_ms"] = percentile(tp, 50) * 1e3
    if kind == "open" and att:
        ttft = ttft_from_due(att, seconds)
        for q in (50, 90, 95):
            out[f"ttft_p{q}_ms"] = percentile(ttft, q) * 1e3
    return {"metrics": out, "attempted": len(att),
            "failed": len(split["failed"]),
            "failed_ids": [r["id"] for r in split["failed"]][:20]}


def client_side(rows, t0: float, seconds: float, kind: str) -> dict:
    """What the per-layer readers take from the client: how late the
    generator sent (send - due) and the mean first-token time from SEND."""
    att = window_rows(rows, t0, t0 + seconds, kind)["attempted"]
    out = {}
    if kind == "open" and att:
        out["late_p95_s"] = percentile([r["send"] - r["due"] for r in att],
                                       95)
    ttft = [r["frames"][0] - r["send"] for r in att if r.get("frames")]
    if ttft:
        out["ttft_from_send_mean_s"] = sum(ttft) / len(ttft)
    gaps = pooled_gaps(rows, t0, t0 + seconds)
    if gaps:
        # the end-to-end itl_p95_ms again: per layer in a cell where it is
        # not end to end (layer_metrics/stream.itl_p95_ms.json)
        out["itl_p95_s"] = percentile(gaps, 95)
        out["itl_p99_s"] = percentile(gaps, 99)
    if kind == "open" and att:
        ttft_due = ttft_from_due(att, seconds)
        for q in (50, 90, 95):
            out[f"ttft_p{q}_s"] = percentile(ttft_due, q)
    return out
