"""Show, on the chip, that a configuration's reference check
(benchmark/checks/reference_logits.py, or reference_logits_<module>.py
where `meta.json` has a `reference_check` key naming the module) is tight:
serve a benchmark configuration exactly as a run does (benchmark/run.py's
own `Served`, launcher and socket), run ONLY that check's measurement, and
print its readings, with the served model or the reference mutated.

    chiprun -- python3 tools/olmoe_reference_probe.py --mutation none
    ... --mutation renorm        the router renormalises its top-k weights
    ... --mutation drop1pct      one (token, expert) assignment in a hundred
                                 never reaches the sum (drop5pct: in twenty)
    ... --mutation ref-float8    nothing served is changed; the REFERENCE's
                                 weights are rounded to float8 (e4m3), the
                                 nearest precision below bfloat16
    ... --mutation rotate-full   a model whose full-attention layers have no
                                 positional embedding (`rope_type` "none")
                                 is served with them rotated as the sliding
                                 layers are
    ... --mutation no-out-gate   softmax attention's output gate is skipped

`--prompt-seeds a,b,c` reads further draws of the prompts,
`--then-float8` the float8 reference and `--then-bf16-state` the reference
with its recurrent state in bfloat16, `--then-controls` every entry of the
check's own `CONTROLS` (a check that names its controls:
reference_logits_falcon_h1.py), all in the one served process (a start
costs minutes at these widths): one JSON line each.
`--served-from FILE` reads the controls alone over what an earlier run of
the cell served (the check leaves it in the run's output directory).

One JSON line: the readings, the check's limits for the served dtype and
`passes`. `none` must pass; PERF.md section 6, PR 27 says which mutations
the check sees at the published widths in bfloat16 and which only the
float32 tier-1 test does. `--rehearsal` runs the tiny
configuration on the CPU (control flow only; with `--float32` the check's
float32 limits apply, and the served-side mutations must fail them).
"""
from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)


def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mutate(kind: str) -> None:
    import jax.numpy as jnp
    from dynamo_tpu.ops import moe
    route = moe.route_topk
    if kind == "renorm":
        moe.route_topk = lambda x, router, k, renorm, *more: route(
            x, router, k, True, *more)
    elif kind.startswith("drop"):
        every = {"drop1pct": 100, "drop5pct": 20}[kind]

        def dropping(x, router, k, renorm, *more):
            weights, idx = route(x, router, k, renorm, *more)
            flat = jnp.arange(weights.size).reshape(weights.shape)
            return jnp.where(flat % every == every // 3, 0.0, weights), idx
        moe.route_topk = dropping
    elif kind == "rotate-full":
        from dynamo_tpu.models import llama
        table = llama.rope_table
        llama.rope_table = lambda cfg, kind="": table(cfg, "swa")
    elif kind == "no-out-gate":
        from dynamo_tpu.models import llama
        llama._out_gate = lambda attn, x, lp, cfg: attn


async def probe(args) -> None:
    import jax.numpy as jnp
    run = load("bench_run", os.path.join(BENCH, "run.py"))
    config_dir = os.path.join(BENCH, "configs", args.config)
    with open(os.path.join(config_dir, "meta.json")) as f:
        meta = json.load(f)
    module = (meta.get("reference_check") or {}).get("module")
    check = load("reference_check", os.path.join(
        BENCH, "checks", f"reference_logits_{module}.py"
        if module else "reference_logits.py"))
    if args.rehearsal:
        config_dir = os.path.join(BENCH, "configs",
                                  meta["rehearsal_config"])
    with open(os.path.join(config_dir, "config.json")) as f:
        model_cfg = json.load(f)
    out_dir = os.path.join(ROOT, "chiprun_out", "reference_probe",
                           args.mutation)
    os.makedirs(out_dir, exist_ok=True)
    model_dir = run.build_model_dir(config_dir,
                                    os.path.join(out_dir, "model"))
    mutate(args.mutation)
    if args.float32:
        # the launcher has no dtype flag (it serves what a deployment
        # serves); here the served ModelConfig alone is made float32
        import dataclasses
        from dynamo_tpu.models import loader
        base = loader.config_from_hf
        loader.config_from_hf = lambda hf, name="": dataclasses.replace(
            base(hf, name), dtype="float32")
    served = run.Served(model_dir, list(meta["serve"]), run.free_port())
    await served.start()
    ctx = run.CheckCtx(served, os.path.basename(model_dir),
                       int(model_cfg["vocab_size"]))
    row = await ctx.request(8, 1, 1, {"temperature": 0.0})
    ctx.template_tokens = row["usage"]["prompt_tokens"] - 8
    def float8(a):
        return a.astype(jnp.float8_e4m3fn).astype(a.dtype)

    seeds = [int(x) for x in args.prompt_seeds.split(",")] \
        if args.prompt_seeds else [args.prompt_seed]
    readings = [(seed, args.mutation) for seed in seeds]
    if args.then_float8:
        readings.append((seeds[0], "ref-float8"))
    if args.then_bf16_state:
        readings.append((seeds[0], "ref-bf16-state"))
    controls = getattr(check, "CONTROLS", {}) if args.then_controls else {}
    readings += [(seeds[0], name) for name in controls]
    served_rows = None
    if args.served_from:
        # what an earlier run served (the check's own file in that run's
        # output directory): the controls alone, one reference pass each
        with open(args.served_from) as f:
            served_rows = json.load(f)
        readings = readings[len(seeds):]
    for seed, mutation in readings:
        if seed is not None:
            check.SEED = seed
        cast = float8 if mutation == "ref-float8" else None
        if hasattr(check, "problems"):
            # the check's own comparison, and the differences themselves
            # beside the line (chiprun_out/reference_probe/<mutation>/)
            diffs = []
            more = {"state_dtype": "bfloat16"} \
                if mutation == "ref-bf16-state" else {}
            if mutation in controls:
                more = dict(controls[mutation])
            elif cast is not None or not controls:
                more["cast"] = cast
            if served_rows is None and hasattr(check, "serve"):
                # a check that also reads the engine's state: serve once,
                # keep it in memory, every reading over it
                served_rows = await check.serve(ctx)
            if served_rows is None and mutation in controls:
                # the controls read what the first reading served
                with open(check.served_path(ctx)) as f:
                    more["served"] = json.load(f)
            elif served_rows is not None:
                more["served"] = served_rows
            got = await check.measure(ctx, keep=diffs, **more)
            with open(os.path.join(
                    out_dir, f"diffs-{mutation}-{seed}.json"), "w") as f:
                json.dump(sorted(diffs), f)
            found = check.problems(got)
            got.update(problems=found, passes=not found)
        else:
            got = await check.measure(ctx, cast=cast)
            largest, median = check.LIMITS[got["dtype"]]
            got.update(passes=got["largest"] < largest
                       and got["median"] < median)
        got.update(mutation=mutation, prompt_seed=seed,
                   limits=check.LIMITS[got["dtype"]],
                   device=served.worker.engine.device_info())
        print(json.dumps(got), flush=True)
        with open(os.path.join(out_dir, "readings.jsonl"), "a") as f:
            f.write(json.dumps(got) + "\n")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--mutation", default="none",
                   choices=("none", "renorm", "drop1pct", "drop5pct",
                            "ref-float8", "rotate-full", "no-out-gate"))
    p.add_argument("--config", default="olmoe-1b-7b")
    p.add_argument("--rehearsal", action="store_true")
    p.add_argument("--float32", action="store_true",
                   help="serve in float32 (with --rehearsal: rounding "
                        "out of the way, the check's float32 limits "
                        "apply and a model served wrong fails them)")
    p.add_argument("--prompt-seed", type=int, default=None,
                   help="draw the check's three prompts from another seed")
    p.add_argument("--prompt-seeds", default="",
                   help="several draws, one reading each, one process")
    p.add_argument("--then-float8", action="store_true",
                   help="after them, the float8 reference on the first draw")
    p.add_argument("--then-bf16-state", action="store_true",
                   help="after them, the reference with its recurrent "
                        "state rounded to bfloat16 (a configuration with "
                        "linear-attention layers)")
    p.add_argument("--then-controls", action="store_true",
                   help="after them, every control the check names in its "
                        "CONTROLS, each over what the first reading served")
    p.add_argument("--served-from", default="",
                   help="the controls alone, over what an earlier run "
                        "served (chiprun_out/benchmark/<cell>/<run>/"
                        "reference_logits_ling.served.json): no serving")
    args = p.parse_args()
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    asyncio.run(probe(args))
    os._exit(0)     # the launcher's tasks have no clean stop from outside


if __name__ == "__main__":
    main()
