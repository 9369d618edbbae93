#!/usr/bin/env python3
"""A benchmark run that leaves its programs' optimised HLO beside the
trace it keeps.

    python3 tools/trace_programs.py --workload mistral-7b.decode-closed \\
        --seed 7 --seconds 51 --trace 1 --keep-trace
    python3 -m dynamo_tpu.observability.profile \\
        chiprun_out/benchmark/mistral-7b.decode-closed/s7-t1/trace

The arguments are `benchmark/run.py`'s own and go to it untouched: this
runs that file as `__main__` in this process. `benchmark/run.py` starts
and stops the profiler itself, so nothing writes the HLO that a trace's
ops are given their scopes from (`NativeEngineWorker.capture_profile`
does, for a capture of its own); here `jax.profiler.stop_trace` is
followed by `NativeEngine.program_texts` of every engine the process
built, into `programs/` beside the `*.xplane.pb`, where
`observability/profile.py` looks. It runs after the traced slice, on the
thread that stops the profiler, and adds nothing to what the run
measures. A builder's tool: run it with an EMPTY compile cache directory
(`JAX_COMPILATION_CACHE_DIR`), because an executable loaded from a cache
that an older tree filled carries that tree's `op_name`s.
"""
import glob
import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    import jax

    from dynamo_tpu.engine import engine as eng
    engines, traced = [], []
    init, start, stop = (eng.NativeEngine.__init__,
                         jax.profiler.start_trace, jax.profiler.stop_trace)

    def remember(self, *args, **kwargs):
        init(self, *args, **kwargs)
        engines.append(self)

    def start_trace(log_dir, *args, **kwargs):
        traced.append((log_dir, [e._dispatch_seq for e in engines]))
        return start(log_dir, *args, **kwargs)

    def stop_trace():
        stop()
        log_dir, since = traced.pop()
        home = os.path.join(os.path.dirname(sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]),
            "programs")
        os.makedirs(home, exist_ok=True)
        for engine, seq in zip(engines, since):
            for name, text in engine.program_texts(seq).items():
                with open(os.path.join(home, name + ".hlo.txt"), "w") as f:
                    f.write(text)

    eng.NativeEngine.__init__ = remember
    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, \
        stop_trace
    sys.argv[0] = os.path.join(ROOT, "benchmark", "run.py")
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
