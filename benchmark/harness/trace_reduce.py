"""From a profiler trace (`*.xplane.pb`) to numbers. Uses only
`jax.profiler.ProfileData`; kept with the benchmark so that every PR
reduces a trace the same way.

What a v5e trace looks like (looked at by hand, PR 23; see PERF.md §5):
one plane per chip named `/device:TPU:<n>`, whose lines include
`XLA Modules` (one event per executed program, named
`jit_<function>(<fingerprint>)`; the engine jits `functools.partial`
objects, which have no name, so today every program is `jit__unknown`) and
`XLA Ops` (one event per HLO op, named by its whole HLO line); host
threads are lines of the `/host:CPU` plane.

`reduce_trace` returns
  window_s     first event start to last event end, over all planes
  busy_s       union of the op intervals, mean over device planes
  chips        number of device planes
  modules      {name: [durations_s]} from device 0's module line
  device_ops   [[name, seconds]] top ops by total time, mean over chips
  idle_gaps    [[label, seconds]] device 0's idle gaps summed by the host
               span that overlaps each most, else "unattributed"
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
NAME_CHARS = 96        # an XLA op's "name" is its whole HLO line
MIN_GAP_S = 20e-6      # shorter gaps are launch latency, not host work


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> list:
    """Merge [(start, end)] into disjoint sorted intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line):
    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in line.events]


def strip_fingerprint(name: str) -> str:
    """`jit__engine_decode_window(123456)` -> `jit__engine_decode_window`."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes) -> dict:
    """`planes`: [(plane name, [(line name, [(start_ns, end_ns, name)])])]."""
    lo, hi = None, None
    devices, host_spans = [], []
    for pname, lines in planes:
        for lname, evs in lines:
            for s, e, _ in evs:
                lo = s if lo is None or s < lo else lo
                hi = e if hi is None or e > hi else hi
        if DEVICE_PLANE.match(pname):
            devices.append((pname, dict(lines)))
        elif HOST_PLANE.match(pname):
            for lname, evs in lines:
                host_spans.extend(evs)
    if lo is None:
        return {}
    out = {"window_s": (hi - lo) / 1e9, "chips": len(devices)}
    if not devices:
        return out
    devices.sort()
    busy, op_time = [], defaultdict(float)
    for _, lines in devices:
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = union((s, e) for s, e, _ in evs)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for s, e, name in lines.get(OPS_LINE, ()):
            op_time[name] += (e - s) / 1e9 / len(devices)
    out["busy_s"] = sum(busy) / len(busy)
    out["all_ops"] = sorted(([n[:NAME_CHARS], t] for n, t in op_time.items()),
                            key=lambda kv: -kv[1])
    out["device_ops"] = out["all_ops"][:TOP]
    dev0 = devices[0][1]
    modules = defaultdict(list)
    for s, e, name in dev0.get(MODULES_LINE, ()):
        modules[strip_fingerprint(name)].append((e - s) / 1e9)
    out["modules"] = dict(modules)
    runs = [d for ds in modules.values() for d in ds]
    if runs:
        out["program_runs"] = len(runs)
        out["program_mean_s"] = sum(runs) / len(runs)
    # idle gaps of device 0, labelled by the host span overlapping most
    merged = union((s, e) for s, e, _ in
                   (dev0.get(OPS_LINE) or dev0.get(MODULES_LINE) or ()))
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if (b[0] - a[1]) / 1e9 >= MIN_GAP_S]
    host_spans.sort()
    starts = [s for s, _, _ in host_spans]
    import bisect
    by_label = defaultdict(float)
    for gs, ge in gaps:
        best, best_ov = "unattributed", 0
        i = bisect.bisect_left(starts, ge)
        for s, e, name in host_spans[max(0, i - 64):i]:
            ov = min(e, ge) - max(s, gs)
            if ov > best_ov:
                best, best_ov = name, ov
        by_label[best] += (ge - gs) / 1e9
    out["idle_gaps"] = [[n[:NAME_CHARS], t] for n, t in sorted(
        by_label.items(), key=lambda kv: -kv[1])[:TOP]]
    out["idle_gap_count"] = len(gaps)
    return out


def load_planes(xplane_path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    return [(p.name, [(ln.name, _events(ln)) for ln in p.lines])
            for p in data.planes]


def reduce_trace(trace_dir: str) -> dict:
    return reduce_planes(load_planes(find_xplane(trace_dir)))


def describe(xplane_path: str, n: int = 4) -> list:
    """Planes, lines and their first events: what to look at by hand
    before trusting the patterns above."""
    out = []
    for pname, lines in load_planes(xplane_path):
        for lname, evs in lines:
            out.append({"plane": pname, "line": lname, "events": len(evs),
                        "first": [[nm, (e - s) / 1e3] for s, e, nm
                                  in evs[:n]]})
    return out
