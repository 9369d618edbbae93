"""Standalone TPU numerical-parity runner (VERDICT r4 #2/#3).

Runs ONLY bench.py's parity phase (bench.run_parity — one shared
implementation, so this always validates the exact configuration the
bench measures) without the perf phases in front of it, so it fits a
short chip call: window engine (decode_steps=64, split-KV pregather +
deferred writeback + adaptive ladder) vs the single-step twin, 96 greedy
tokens, token-for-token. CPU tests can't see Mosaic/XLA-TPU divergence —
this check must execute on hardware.

Shares the persistent compilation cache with every other entry point
(dynamo_tpu/utils/launch.py), so a run after a bench capture in the same
place only pays the single-step twin's compile. Writes PARITY_TPU_r05.json
and exits 0 on exact parity, 1 on divergence, 2 when the backend is not a
TPU, 3 on a configuration error.

Reference bar: the window decode path is our throughput headline
(docs/architecture.md:57-61 analogue); an unnoticed numerics divergence
there would invalidate it.
"""
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
# PARITY_OUT: alternate artifact name so variant captures (e.g. an int8
# parity run) don't overwrite the bf16 evidence
OUT = os.path.join(HERE, os.environ.get("PARITY_OUT",
                                        "PARITY_TPU_r05.json"))


def log(*a):
    print("[parity]", *a, file=sys.stderr, flush=True)


def main() -> int:
    t0 = time.time()
    import jax

    from dynamo_tpu.utils.launch import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    backend = jax.default_backend()
    log(f"backend up in {time.time() - t0:.1f}s: {devices} ({backend})")
    if backend != "tpu" and os.environ.get("PARITY_ALLOW_CPU") != "1":
        log("not a TPU backend; refusing (set PARITY_ALLOW_CPU=1 to force)")
        return 2

    import bench
    from dynamo_tpu.engine.config import get_model_config

    model_cfg = get_model_config(os.environ.get("BENCH_MODEL", "llama3-1b"))
    # honor BENCH_QUANT exactly as the bench worker does, so an int8
    # capture can get int8 parity evidence (not a bf16 run mislabeled)
    quant = os.environ.get("BENCH_QUANT", "")
    if quant:
        if quant != "int8":
            log(f"BENCH_QUANT={quant!r} unsupported (supported: int8)")
            return 3  # config error
        import dataclasses
        model_cfg = dataclasses.replace(model_cfg, quant=quant)
    # PARITY_DECODE_KERNEL=on: run the window-vs-single-step check with the
    # ragged Pallas decode kernel instead of the serving-default XLA gather
    # (models/llama._decode_kernel_mode), so the kernel path gets its own
    # token-for-token hardware evidence.
    dk = os.environ.get("PARITY_DECODE_KERNEL", "")
    if dk:
        if dk not in ("on", "interpret"):
            log(f"PARITY_DECODE_KERNEL={dk!r} unsupported "
                "(supported: on, interpret)")
            return 3
        import dataclasses
        model_cfg = dataclasses.replace(model_cfg, decode_kernel=dk)
    # PARITY_KV_QUANT=int8: run the kv-cache quantization gate instead of
    # the window-vs-single-step check — greedy-match rate + bounded logit
    # drift between the int8-KV engine and its unquantized twin, the SAME
    # bench.run_kv_quant_parity implementation (and thresholds) the tier-1
    # gate runs on CPU (tests/test_kv_quant.py), now on real hardware.
    kvq = os.environ.get("PARITY_KV_QUANT", "")
    if kvq:
        if kvq != "int8":
            log(f"PARITY_KV_QUANT={kvq!r} unsupported (supported: int8)")
            return 3
        verdict = bench.run_kv_quant_parity(model_cfg, logf=log)
    else:
        verdict = bench.run_parity(model_cfg, logf=log)
    record = {
        "t": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "backend": backend, "devices": [str(d) for d in devices],
        "parity": verdict, "window_decode_steps": 64,
        "elapsed_s": round(time.time() - t0, 1),
    }
    if quant:
        record["quant"] = quant
    if dk:
        record["decode_kernel"] = dk
    if kvq:
        record["kv_quant"] = kvq
    # evidence-artifact policy (tools/artifacts.py, VERDICT r5 weak #7):
    # final name, written once; a re-run of the same capture overwrites
    # deliberately rather than renaming the old file aside
    from tools.artifacts import write_json
    write_json(OUT, record, overwrite=True)
    log(f"wrote {OUT}")
    if kvq:
        return 0 if verdict.get("pass") else 1
    return 0 if verdict.startswith("exact") else 1


if __name__ == "__main__":
    sys.exit(main())
