"""The load generator: a child process that NEVER imports JAX (the runner
holds the chip). Seeded, asyncio, one HTTP connection per request to the
real socket, streaming with `stream_options.include_usage` and
`ext.ignore_eos`.

    python benchmark/harness/loadgen.py <plan.json>

The plan (written by run.py) names the port, the mix, the cell, the seed,
the window length and the engine's bucket ladders. The child

 1. runs the warm-up: a deterministic walk over every (program, bucket)
    the mix can reach (see `Warmup`), printing `{"event": "warm_done"}`;
 2. reads one line `{"t0": <CLOCK_MONOTONIC seconds>}` from stdin;
 3. runs the window [t0, t0 + seconds): open mixes send each request at
    t0 + due whatever happened to the earlier ones, and time it from the
    DUE time (a mix with `lead_in_s` has requests due before t0, and t0
    lies that far ahead); closed mixes keep their clients running;
 4. writes one JSON row per request to `rows_path` and prints
    `{"event": "done", ...}`.

Clocks: `time.monotonic()` is CLOCK_MONOTONIC, one clock for every process
of the machine, so the runner's snapshots and these rows share it.
"""
from __future__ import annotations

import asyncio
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import traffic   # noqa: E402  (jax-free by construction)

REQUEST_TIMEOUT_S = 120.0
DRAIN_S = 45.0


class Row(dict):
    """One request's record. Times are CLOCK_MONOTONIC seconds."""


async def do_request(port: int, model: str, req: dict, row: Row,
                     first_token: asyncio.Event = None) -> Row:
    """POST one streaming chat completion; fills `row` in place so a
    cancelled request still leaves what it saw."""
    body = {"model": model, "max_tokens": req["max_tokens"], "stream": True,
            "seed": req["seed"], "ext": {"ignore_eos": True},
            "stream_options": {"include_usage": True},
            "messages": [{"role": "user", "content": req["content"]}],
            **req["sampling"], **req.get("extra", {})}
    payload = json.dumps(body).encode()
    row.update(prompt_tokens=req["prompt_tokens"],
               max_tokens=req["max_tokens"], frames=[], status=None,
               finish=None, usage=None, error=None, send=time.monotonic())
    writer = None
    try:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(
            b"POST /v1/chat/completions HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(payload)).encode() + b"\r\n\r\n" + payload)
        await writer.drain()
        status = await reader.readline()
        row["status"] = int(status.split()[1])
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        if row["status"] != 200:
            row["error"] = (await reader.read(300)).decode(errors="replace")
            return row
        while True:
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                break
            now = time.monotonic()
            chunk = json.loads(data)
            for ch in chunk.get("choices") or ():
                if (ch.get("delta") or {}).get("content"):
                    row["frames"].append(now)
                    if first_token is not None:
                        first_token.set()
                if ch.get("logprobs") and "logprobs" in row:
                    row["logprobs"].extend(ch["logprobs"].get("content")
                                           or ())
                if ch.get("finish_reason"):
                    row["finish"] = ch["finish_reason"]
            if chunk.get("usage"):
                row["usage"] = chunk["usage"]
        row["end"] = time.monotonic()
        return row
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        row["error"] = f"{type(e).__name__}: {e}"
        return row
    finally:
        if first_token is not None:
            first_token.set()
        if writer is not None:
            writer.close()


def rung_for(nd: int, ladders: dict) -> int:
    """The prefill-chunk bucket a mixed step takes beside nd decode rows:
    the largest with bucket * (nd + 1) <= budget, else the smallest.

    This, `row_bucket` and `group_levels` are a COPY of the scheduler's
    rule (`Scheduler._schedule_mixed` / `_build_prefill`), over ladders
    read from the live engine: the program has no call that says which
    programs a mix can reach, or that warms them. A PR that changes the
    rule makes this walk warm the wrong programs; the run then counts them
    in `warmup.compiles_in_window` and is not `correct`. The cure is the
    program's own warm-the-ladder call, which replaces the walk (PERF.md
    section 7); until it lands, a change of the rule needs a `benchmark`
    PR beside it."""
    for b in sorted(ladders["prefill_buckets"], reverse=True):
        if b * (nd + 1) <= ladders["mixed_token_budget"]:
            return b
    return min(ladders["prefill_buckets"])


def row_bucket(n_rows: int, ladders: dict) -> int:
    cap = ladders["max_slots"] + max(1, ladders["max_prefill_batch"])
    b = 1
    while b < n_rows and b < cap:
        b *= 2
    return min(b, cap)


def group_levels(ladders: dict, upto: int) -> list:
    """The first decode-row count of each distinct (chunk rung, row bucket)
    group a mixed step can take, for 1..upto running rows."""
    seen, out = set(), []
    for nd in range(1, upto + 1):
        key = (rung_for(nd, ladders), row_bucket(nd + 1, ladders))
        if key not in seen:
            seen.add(key)
            out.append(nd)
    return out


def probe_lengths(rung: int, ladders: dict, min_prompt: int) -> list:
    """Prompt lengths whose chunks beside a fixed number of decode rows
    cover every chunk bucket up to `rung`: whole rungs first, then a last
    chunk that fills one bucket exactly. Always two chunks or more: with
    every slot taken, only a chunk that is not the last rides a step."""
    k = -(-max(min_prompt, 1) // rung)
    return [k * rung + b for b in sorted(ladders["prefill_buckets"])
            if b <= rung]


class Warmup:
    """Dispatch every (program, bucket) the mix's traffic can reach, before
    the window. The engine compiles one program per
    (rows bucket, chunk bucket, page-table width) mixed step and per
    (admission width, live KV width, window rung) decode window; a first
    dispatch inside the window is a compile that stalls every stream. So:

      - holders (closed-loop clients of the mix) are added one at a time,
        each when the last streamed its first token, so the number of
        decode rows walks 1, 2, 3 ... deterministically;
      - at the first row count of each (chunk rung, rows bucket) group,
        short probes run one at a time beside the holders: their prompt
        lengths fill every chunk bucket up to the rung, and their
        max_tokens of 2 and 3 end on the 1-step and the 2-step window rung;
      - the mix pins the admission width to one bucket and names the
        holders that walk the live KV width through its buckets.

    Everything is fixed by the mix, the cell and the ladders, never by
    `--seed` or the clock, so every run of a cell dispatches the same
    programs and the persistent cache holds them after the first."""

    def __init__(self, gen: "LoadGen"):
        self.g = gen
        self.probe_n = 0

    async def probe(self, prompt_tokens: int, max_tokens: int) -> None:
        g = self.g
        self.probe_n += 1
        req = g.make_request(prompt_tokens, max_tokens, 7000 + self.probe_n)
        row = g.new_row("probe", None)
        await do_request(g.port, g.model, req, row)

    async def level_probes(self, nd: int, beside: int) -> None:
        """The probes of the group that starts at `nd` decode rows, run
        beside `beside` of them. Twice over: a holder that ends under a
        probe moves the row count for that probe, hardly for the same
        probe of both passes; the second pass loads nothing and is cheap."""
        g = self.g
        lens = probe_lengths(rung_for(nd, g.ladders), g.ladders,
                             g.template_tokens + 1)
        for i, n in enumerate(lens * 2):
            await g.wait_decoding(beside)
            await self.probe(n, 2 + (i + i // len(lens)) % 2)

    async def idle_probes(self) -> None:
        """Open mixes can find the engine idle: pure prefill steps, whose
        chunk buckets and table width come from the mix's own prompts."""
        g = self.g
        lo, hi = traffic.bounds(g.mix["prompt_tokens"])
        cap = g.ladders["max_prefill_chunk"]
        buckets = sorted(g.ladders["prefill_buckets"])
        need = {}
        for n in range(lo, hi + 1):
            chunks, left = [], n
            while left > 0:
                c = min(left, cap)
                chunks.append(next(b for b in buckets if b >= c))
                left -= c
            for b in chunks:
                need.setdefault(b, n)
        for i, n in enumerate(sorted(set(need.values()))):
            await self.probe(n, 2 + i % 2)

    async def run(self) -> None:
        g = self.g
        warm = g.mix.get("warmup", {})
        if g.mix["kind"] == "closed":
            target = int(g.cell["clients"])
        else:
            target = int(warm.get("holders", 16))
        if g.mix["kind"] == "open":
            await self.idle_probes()
        levels = group_levels(g.ladders, target)
        if g.mix["kind"] == "closed":
            # a closed loop never leaves the row counts next to `clients`:
            # the lower groups are passed on the way up (their programs
            # compile then, in set-up) and need no probes
            floor = target - int(warm.get("probe_within", 2))
            levels = [nd for i, nd in enumerate(levels)
                      if i + 1 == len(levels) or levels[i + 1] > floor]
            # ... but the short window rungs are hit at a quiet moment
            # only: one holder decoding, nothing else being admitted
            await g.add_client()
            n = 2 * rung_for(1, g.ladders)
            await self.probe(n, 2)
            await self.probe(n, 3)
        for i, nd in enumerate(levels):
            # one row above the group's first count where the group has
            # room, so that one holder between two requests leaves the
            # count inside the group
            top = levels[i + 1] - 1 if i + 1 < len(levels) else nd
            beside = nd if g.mix["kind"] == "closed" else min(nd + 1, top)
            while len(g.clients) < beside:
                await g.add_client()
            if g.mix["kind"] == "closed":
                await g.wait_decoding(min(nd, g.ladders["max_slots"] - 1))
                await self.probe(2 * rung_for(nd, g.ladders), 2)
            else:
                await self.level_probes(nd, beside)
        if g.mix["kind"] == "open":
            await g.stop_clients(wait=False)


class LoadGen:
    def __init__(self, plan: dict):
        self.plan = plan
        self.port, self.model = plan["port"], plan["model"]
        self.mix, self.cell = plan["mix"], plan["cell"]
        self.ladders = plan["ladders"]
        self.template_tokens = plan["template_tokens"]
        self.vocab = plan["vocab"]
        self.rows: list = []
        self.clients: list = []      # closed-loop client tasks
        self.stopping = False
        self.current: dict = {}      # client -> the row it is streaming
        self.phase = "warmup"
        self.sched = traffic.schedule(
            self.mix, self.cell, plan["seed"], plan["seconds"],
            self.template_tokens, self.vocab)
        self.pool_next = 0
        self.warm_next = 0
        self.words = random.Random(12345)   # warm-up prompts: never the seed

    def new_row(self, kind: str, client) -> Row:
        row = Row(id=len(self.rows), kind=kind, client=client,
                  phase=self.phase)
        self.rows.append(row)
        return row

    def make_request(self, prompt_tokens: int, max_tokens: int,
                     seed: int) -> dict:
        samp = self.mix["sampling"][0]
        return {"prompt_tokens": prompt_tokens, "max_tokens": max_tokens,
                "seed": seed,
                "sampling": {"temperature": samp["temperature"],
                             "top_p": samp["top_p"]},
                "content": traffic.prompt_words(
                    self.words, prompt_tokens - self.template_tokens,
                    self.vocab)}

    def first_request(self, k: int) -> dict:
        """Client k's first request. A closed mix starts its clients as if
        mid-request: `warmup.stagger` fixes prompt + max_tokens and lets
        the prompt fall from `prompt_hi` to `prompt_lo` with k (squared, so
        that the long prompts come while few rows decode and a chunk is
        still wide), which spreads contexts and ends from the start.
        `warmup.first_holder` is the row that puts the live KV width in
        the mix's bucket at once; an open mix's holders are all
        `warmup.holder` after it."""
        warm = self.mix.get("warmup", {})
        if k == 0 and "first_holder" in warm:
            h = warm["first_holder"]
            return self.make_request(h["prompt_tokens"], h["max_tokens"],
                                     9000)
        if k < int(warm.get("long_holders", 0)):
            # a second long row, half a life out of phase with the first
            h = warm["first_holder"]
            return self.make_request(h["prompt_tokens"],
                                     h["max_tokens"] // 2, 9000 + k)
        if "stagger" in warm:
            st, n = warm["stagger"], max(2, int(self.cell["clients"]))
            frac = (1.0 - k / (n - 1)) ** 2
            prompt = int(st["prompt_lo"]
                         + (st["prompt_hi"] - st["prompt_lo"]) * frac)
            # whole chunks only: client k is admitted beside k decode
            # rows, and a remainder chunk would be one more program to
            # load in every run's set-up (~3 s each on a v5e)
            rung = rung_for(k, self.ladders)
            prompt = max(rung, prompt // rung * rung)
            return self.make_request(prompt, int(st["total"]) - prompt,
                                     9000 + k)
        return self.next_warm()

    def next_warm(self) -> dict:
        """A holder's request in an open mix, `warmup.holder`: a short
        prompt and a long answer inside the mix's admission bucket, so the
        row counts hold still under the probes."""
        h = self.mix["warmup"]["holder"]
        self.warm_next += 1
        return self.make_request(h["prompt_tokens"], h["max_tokens"],
                                 8000 + self.warm_next)

    def next_pool(self) -> dict:
        pool = self.sched["pool"]
        req = pool[self.pool_next % len(pool)]
        self.pool_next += 1
        return req

    async def client_loop(self, k: int, started: asyncio.Event) -> None:
        first = True
        while not self.stopping:
            if first:
                req = self.first_request(k)
            elif self.mix["kind"] == "closed":
                req = self.next_pool()
            elif k < int(self.mix.get("warmup", {}).get("long_holders", 0)):
                req = self.first_request(0)   # stays the longest row
            else:
                req = self.next_warm()
            row = self.current[k] = self.new_row("client", k)
            await do_request(self.port, self.model, req, row,
                             started if first else None)
            first = False
            if row["error"] or row["status"] != 200:
                await asyncio.sleep(0.05)   # never spin on a dead server

    async def wait_decoding(self, n: int, timeout: float = 60.0) -> None:
        """Until n clients are mid-stream (first token seen, not ended):
        a probe then runs beside exactly n decode rows."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            live = sum(1 for r in self.current.values()
                       if r["frames"] and "end" not in r and not r["error"])
            if live >= n:
                return
            await asyncio.sleep(0.002)

    async def add_client(self) -> None:
        started = asyncio.Event()
        k = len(self.clients)
        self.clients.append(asyncio.create_task(
            self.client_loop(k, started)))
        await started.wait()

    async def stop_clients(self, wait: bool) -> None:
        self.stopping = True
        if not wait:
            for t in self.clients:
                t.cancel()
        await asyncio.gather(*self.clients, return_exceptions=True)
        self.clients, self.current = [], {}
        self.stopping = False

    async def run_open(self, t0: float) -> None:
        async def one(req):
            delay = t0 + req["due"] - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            row = self.new_row("open", None)
            row["due"] = t0 + req["due"]
            row["phase"] = self.phase
            await do_request(self.port, self.model, req, row)
        tasks = [asyncio.create_task(one(r)) for r in self.sched["requests"]]
        done, pending = await asyncio.wait(
            tasks, timeout=self.sched["lead_in_s"] + self.plan["seconds"]
            + DRAIN_S)
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)

    async def main(self) -> None:
        t_w = time.monotonic()
        await Warmup(self).run()
        print(json.dumps({"event": "warm_done",
                          "warm_s": time.monotonic() - t_w,
                          "warm_requests": len(self.rows)}), flush=True)
        loop = asyncio.get_running_loop()
        window = 0
        while True:
            # one line per window: {"t0": ...}; a rate sweep (run.py
            # --sweep) sends several, each with its own "rate_per_s"
            line = await loop.run_in_executor(None, sys.stdin.readline)
            go = json.loads(line) if line.strip() else {"stop": True}
            if go.get("stop"):
                break
            window += 1
            self.phase = "window" if window == 1 else f"window{window}"
            if self.mix["kind"] == "open":
                if "rate_per_s" in go:
                    self.sched = traffic.schedule(
                        self.mix, {"rate_per_s": go["rate_per_s"]},
                        self.plan["seed"] + window, self.plan["seconds"],
                        self.template_tokens, self.vocab)
                await self.run_open(float(go["t0"]))
            else:
                # cut the clients a little AFTER the window: the runner
                # reads its end-of-window counters first, and what the
                # thinning batch compiles is not the window's
                await asyncio.sleep(max(0.0, float(go["t0"])
                                        + self.plan["seconds"] + 1.5
                                        - time.monotonic()))
                await self.stop_clients(wait=False)
            with open(self.plan["rows_path"], "w") as f:
                for row in self.rows:
                    f.write(json.dumps(row) + "\n")
            print(json.dumps({"event": "done", "rows": len(self.rows)}),
                  flush=True)


def main() -> int:
    if "jax" in sys.modules:
        raise SystemExit("the load generator must not import jax")
    with open(sys.argv[1]) as f:
        plan = json.load(f)
    asyncio.run(LoadGen(plan).main())
    if "jax" in sys.modules:
        raise SystemExit("the load generator imported jax")
    return 0


if __name__ == "__main__":
    sys.exit(main())
