"""Set-up check: the served path is deterministic and self-consistent.

The same greedy request with `logprobs` / `top_logprobs=5`, sent twice,
returns identical logprob sequences; every chosen token's logprob is the
largest of its five; all are finite.

NOT checked here: logits against a float32 reference at the published
widths. That reference is ROADMAP R0 and does not exist yet; the PR that
lands it adds `checks/reference_logits.py` and edits nothing here.
"""
from __future__ import annotations

import math

N_TOKENS = 8


def applies(config_meta: dict) -> bool:
    return True


async def run(ctx) -> list:
    """Problems found, as strings; empty when the check passes."""
    bad, seqs = [], []
    for attempt in range(2):
        row = await ctx.request(
            prompt_tokens=40, max_tokens=N_TOKENS, seed=1234,
            sampling={"temperature": 0.0},
            extra={"logprobs": True, "top_logprobs": 5})
        if row.get("status") != 200 or row.get("error"):
            return [f"logprobs request failed: {row.get('status')} "
                    f"{row.get('error')}"]
        ents = row.get("logprobs") or []
        if len(ents) != N_TOKENS:
            bad.append(f"{len(ents)} logprob entries for {N_TOKENS} tokens")
        for e in ents:
            tops = [t["logprob"] for t in e["top_logprobs"]]
            if not all(math.isfinite(x) for x in [e["logprob"], *tops]):
                bad.append("non-finite logprob")
            elif len(tops) != 5 or abs(e["logprob"] - max(tops)) > 1e-4:
                bad.append("greedy token is not the largest of its five")
        seqs.append([e["logprob"] for e in ents])
    if seqs[0] != seqs[1]:
        bad.append("the same greedy request returned different logprobs")
    return bad
