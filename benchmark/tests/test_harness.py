"""By hand, on the CPU: `python -m pytest benchmark/tests -q`.
Not part of the repo's tier-1 suite (tests/)."""
import functools
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
from harness import loadgen, readers, shapes, stats, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LADDERS = {"page_size": 64, "prefill_buckets": [16, 32, 64, 128, 256, 512],
           "mixed_token_budget": 512, "max_prefill_chunk": 512,
           "max_prefill_batch": 8, "max_slots": 32, "decode_steps": 8,
           "page_buckets": [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64]}


def bench_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the generator ----------------------------------------------------------

@pytest.mark.parametrize("mix_name,cell", [
    ("decode-closed", {"clients": 32}), ("chat-open", {"rate_per_s": 5.0}),
    ("decode-closed-wide", {"clients": 32}),
    ("chat-open-wide", {"rate_per_s": 5.0}),
    ("chat-burst", {"rate_per_s": 5.0})])
def test_generator_is_a_pure_function_of_mix_and_seed(mix_name, cell):
    mix = traffic.load_mix(mix_name, HERE)
    a = traffic.schedule(mix, cell, 7, 40.0, 2, 32000)
    b = traffic.schedule(mix, cell, 7, 40.0, 2, 32000)
    c = traffic.schedule(mix, cell, 2**31 + 5, 40.0, 2, 32000)
    assert a == b and a != c
    key = "requests" if mix["kind"] == "open" else "pool"
    # another seed: the same SET of sizes, other words; in another order
    # unless the mix fixes it
    sizes = lambda s: [(r["prompt_tokens"], r["max_tokens"],  # noqa
                        r["sampling"]["temperature"]) for r in s[key]]
    assert sorted(sizes(a)) == sorted(sizes(c))
    assert (sizes(a) == sizes(c)) == (mix.get("order") == "fixed")
    assert a[key][0]["content"] != c[key][0]["content"]
    for r in a[key]:
        assert len(r["content"].split()) == r["prompt_tokens"] - 2
    if mix["kind"] == "open":
        dues = [r["due"] for r in a[key]]
        lead = mix.get("lead_in_s", 0)
        assert dues == sorted(dues) and dues[0] == -lead and dues[-1] < 40.0
        # the window always holds the same requests on the same gaps,
        # whatever the seed; the lead-in is a set of its own before it
        win = lambda s: [r for r in s[key] if r["due"] >= 0]  # noqa
        assert len(win(a)) == 200 and win(a)[0]["due"] == 0.0
        assert len(dues) - 200 == round(5.0 * lead)
        assert sorted(r["max_tokens"] for r in win(a)) == \
            sorted(r["max_tokens"] for r in win(c))
        gaps = lambda s: sorted(round(y["due"] - x["due"], 9)  # noqa
                                for x, y in zip(win(s), win(s)[1:]))
        # the same set of gaps but for the first, which opens the window
        assert abs(sum(gaps(a)) - sum(gaps(c))) < 40.0 / 10
        assert ([r["due"] for r in c[key]] == dues) == \
            (mix.get("order") == "fixed")
    if "admission_pages" in mix:
        assert traffic.check_admission(mix, 64, LADDERS["page_buckets"]) \
            == mix["admission_pages"]
        bad = dict(mix, admission_pages=16)
        with pytest.raises(ValueError, match="spans page buckets"):
            traffic.check_admission(bad, 64, LADDERS["page_buckets"])
    else:   # a mix need not pin its bucket: it is taken as it is
        assert traffic.check_admission(mix, 64, LADDERS["page_buckets"]) > 12


def test_bursts_and_shared_prefixes_are_data(tmp_path):
    """ISSUE 23's next mixes as files only: on/off bursts at the same mean
    rate, and requests that share a prefix."""
    mix = dict(traffic.load_mix("chat-open", HERE), lead_in_s=10,
               order="seed")
    burst = dict(mix, name="chat-burst", arrivals="onoff",
                 burst={"on_s": 2.0, "off_s": 6.0})
    plain = traffic.schedule(mix, {"rate_per_s": 5.0}, 3, 40.0, 2, 32000)
    s = traffic.schedule(burst, {"rate_per_s": 5.0}, 3, 40.0, 2, 32000)
    assert len(s["requests"]) == len(plain["requests"]) == 250   # 10 s lead
    gaps = traffic.gap_set(burst, 200, 40.0)
    assert 32.0 < sum(gaps) <= 40.0 + 1e-9
    t = 0.0
    for g in gaps:      # every arrival in the first 2 s of its 8 s
        t += g
        assert (t - 1e-9) % 8.0 <= 2.0
    with pytest.raises(ValueError, match="unknown arrivals"):
        traffic.gap_set(dict(mix, arrivals="daily"), 10, 40.0)
    shared = dict(mix, shared_prefix={"groups": 3, "tokens": 300})
    s = traffic.schedule(shared, {"rate_per_s": 5.0}, 3, 40.0, 2, 32000)
    heads = {" ".join(r["content"].split()[:300]) for r in s["requests"]}
    assert len(heads) == 3       # the lead-in shares the window's prefixes
    assert len({" ".join(r["content"].split()[:301])
                for r in s["requests"]}) > 100
    for r in s["requests"]:
        assert len(r["content"].split()) == r["prompt_tokens"] - 2


def test_generator_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "from harness import loadgen, traffic, stats; "
            "assert 'jax' not in sys.modules" % HERE)
    subprocess.run([sys.executable, "-c", code], check=True)


def test_unknown_generator_kind_names_the_file(tmp_path):
    root = tmp_path / "b"
    shutil.copytree(os.path.join(HERE, "traffic"), root / "traffic")
    bad = json.load(open(root / "traffic" / "chat-open.json"))
    bad["kind"] = "bursty"
    json.dump(bad, open(root / "traffic" / "odd.json", "w"))
    with pytest.raises(ValueError, match="odd.json"):
        traffic.load_mix("odd", str(root))
    with pytest.raises(FileNotFoundError, match="nope.json"):
        traffic.load_mix("nope", str(root))


def test_warmup_ladder_enumeration():
    assert loadgen.group_levels(LADDERS, 32) == [1, 2, 4, 8, 16, 32]
    assert [loadgen.rung_for(n, LADDERS) for n in (0, 1, 2, 3, 7, 15, 31, 32)
            ] == [512, 256, 128, 128, 64, 32, 16, 16]
    assert loadgen.row_bucket(33, LADDERS) == 40
    assert loadgen.probe_lengths(64, LADDERS, 3) == [80, 96, 128]
    assert loadgen.probe_lengths(16, LADDERS, 3) == [32]


# -- metric arithmetic ------------------------------------------------------

def row(i, due, send, frames, n, ok=True, end=True):
    return {"id": i, "kind": "open", "phase": "window", "due": due,
            "send": send, "frames": frames, "prompt_tokens": 10,
            "max_tokens": n, "status": 200 if ok else 500,
            "finish": "length" if ok else None, "error": None,
            "usage": {"prompt_tokens": 10, "completion_tokens": n},
            **({"end": frames[-1]} if end and frames else {})}


def test_percentile_and_pooled_gaps_by_hand():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([5], 99) == 5
    assert stats.percentile(list(range(101)), 95) == 95
    rows = [row(0, 100.0, 100.0, [100.5, 100.5, 100.5, 101.0], 4),
            row(1, 101.0, 101.2, [102.0, 102.25], 2)]
    gaps = sorted(stats.pooled_gaps(rows, 100.0, 110.0))
    assert gaps == [0.0, 0.0, 0.25, 0.5]
    assert stats.tokens_in_window(rows, 100.0, 102.1) == 5
    long = row(2, 100.0, 100.0, [100.0 + 0.1 * i for i in range(40)], 40)
    assert stats.tpot_per_request(rows + [long], 100.0, 102.05) == [
        pytest.approx(0.1)]                       # 21 frames in the window
    assert stats.tpot_per_request(rows, 100.0, 110.0) == []


def test_failed_requests_count_as_misses():
    rows = [row(i, 100.0 + i, 100.0 + i, [100.2 + i, 100.3 + i], 2)
            for i in range(9)]
    rows.append(row(9, 109.0, 109.0, [], 2, ok=False))
    out = stats.end_to_end(rows, 100.0, 10.0, "open", 1)
    assert out["attempted"] == 10 and out["failed"] == 1
    # nine firsts at 0.2 s, the failure counts as the whole window (10 s)
    assert out["metrics"]["ttft_p95_ms"] == pytest.approx(
        stats.percentile([200.0] * 9 + [10000.0], 95))
    assert out["metrics"]["ttft_p50_ms"] == pytest.approx(200.0)
    short = row(10, 105.0, 105.0, [105.1], 2)   # one frame short of two
    assert not stats.request_ok(short)
    side = stats.client_side(rows, 100.0, 10.0, "open")
    assert side["late_p95_s"] == 0.0
    # the per-layer tail of a cell where `itl_p95_ms` is not end to end is
    # the end-to-end arithmetic on the same rows (stream.itl_p95_ms)
    assert side["itl_p95_s"] * 1e3 == out["metrics"]["itl_p95_ms"]
    assert side["itl_p95_s"] <= side["itl_p99_s"]


def test_closed_window_cuts_unfinished_requests():
    done = dict(row(0, None, 99.0, [99.5, 103.0], 2), kind="client")
    cut = dict(row(1, None, 104.0, [104.5], 2, end=False), kind="client")
    out = stats.end_to_end([done, cut], 100.0, 10.0, "closed", 1)
    assert out["attempted"] == 1 and out["failed"] == 0
    assert out["metrics"]["output_tok_s"] == 0.2


# -- the readers ------------------------------------------------------------

PROM_A = """# HELP llm_engine_tokens_useful x
llm_engine_tokens_useful 100
llm_engine_tokens_padded 400
llm_engine_recompiles 30
llm_ttft_seconds_sum{model="m",qos="standard"} 1.0
llm_ttft_seconds_count{model="m",qos="standard"} 10
"""
PROM_B = PROM_A.replace(" 100", " 400").replace(" 400\nllm_engine_rec",
                                                " 1000\nllm_engine_rec") \
    .replace("} 1.0", "} 4.0").replace("} 10", "} 20")


def ctx(**kw):
    base = {"prom": (readers.parse_prom(PROM_A), readers.parse_prom(PROM_B)),
            "engine": ({"decode_stall_steps": 1, "engine_steps": 10,
                        "pipeline_overlapped": 2, "pipeline_windows": 4},
                       {"decode_stall_steps": 3, "engine_steps": 50,
                        "pipeline_overlapped": 32, "pipeline_windows": 44}),
            "client": {"late_p95_s": 0.002, "ttft_from_send_mean_s": 0.35,
                       "itl_p99_s": 0.4, "ttft_p95_s": 2.5,
                       "ttft_p90_s": 2.25, "ttft_p50_s": 1.5},
            "peak": {"hbm_bytes_per_s": 819e9},
            "run": {"decode_steps": 8, "chips": 1,
                    "decode_step_bytes": 8.19e9},
            "trace": {"busy_s": 3.0, "window_s": 4.0, "chips": 1,
                      "program_mean_s": 0.85 / 6,
                      "modules": {"jit_engine_decode_window_full": [0.16] * 5,
                                  "jit_engine_decode_window_w2": [0.05],
                                  "jit_engine_step": [0.05]},
                      "all_ops": [["fusion.1", 2.0], ["all-reduce.3", 0.6]]}}
    base.update(kw)
    return base


@pytest.mark.parametrize("name,want", [
    ("sched.pad_frac", 100 * (1 - 300 / 600)),
    ("warmup.compiles_in_window", 0.0),
    ("frontend.ttft_gap_ms", 1000 * (0.35 - 0.3)),
    ("loadgen.late_p95_ms", 2.0),
    ("stream.itl_p99_ms", 400.0),
    ("stream.ttft_p95_ms", 2500.0),
    ("stream.ttft_p90_ms", 2250.0),
    ("stream.ttft_p50_ms", 1500.0),
    ("warmup.programs_loaded", 30.0),
    ("device.program_ms", 141.66666666666666),
    ("sched.decode_stall_share", 5.0),
    ("host.overlap_share", 75.0),
    ("device.idle_share", 25.0),
    ("device.window_step_ms", 20.0),      # the full rung's 0.16 s / 8
    ("device.window_roofline", 50.0),
    ("device.window_rung_steps", 8.0),
    ("device.collective_share", 20.0)])
def test_layer_metric_files_on_recorded_sources(name, want):
    spec = readers.load_metric(name, HERE)
    assert readers.evaluate(spec["expr"], ctx()) == pytest.approx(want)
    # a reader that finds nothing to read returns nothing
    empty = {"prom": ({}, {}), "engine": ({}, {}), "trace": {},
             "client": {}, "peak": {}, "run": {}}
    assert readers.evaluate(spec["expr"], empty) is None


def test_unknown_reader_kind_names_the_file(tmp_path):
    os.makedirs(tmp_path / "layer_metrics")
    json.dump({"reader": "spans"},
              open(tmp_path / "layer_metrics" / "x.json", "w"))
    with pytest.raises(ValueError, match="x.json"):
        readers.load_metric("x", str(tmp_path))
    with pytest.raises(FileNotFoundError, match="y.json"):
        readers.load_metric("y", str(tmp_path))


def test_trace_reduction_on_a_synthetic_xplane():
    ms = 1_000_000
    planes = [
        ("/device:TPU:0", [
            ("XLA Modules", [(0, 10 * ms, "jit__engine_decode_window(77)"),
                             (14 * ms, 20 * ms, "jit__engine_step(5)")]),
            ("XLA Ops", [(0, 4 * ms, "fusion.1"), (3 * ms, 10 * ms, "copy.2"),
                         (14 * ms, 20 * ms, "fusion.1")])]),
        ("/device:TPU:1", [
            ("XLA Ops", [(0, 8 * ms, "fusion.1"),
                         (12 * ms, 20 * ms, "all-reduce.3")])]),
        ("/host:CPU", [("python", [(9 * ms, 15 * ms, "commit"),
                                   (0, 40 * ms, "idle-loop")])]),
    ]
    out = trace_reduce.reduce_planes(planes)
    assert out["chips"] == 2 and out["window_s"] == pytest.approx(0.040)
    assert out["busy_s"] == pytest.approx(0.016)      # (16 + 16) / 2 ms
    assert out["modules"]["jit__engine_decode_window"] == [0.010]
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.009)]
    assert out["idle_gaps"] == [["idle-loop", pytest.approx(0.004)]]
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert trace_reduce.reduce_planes([("/host:CPU", [])]) == {}


def test_trace_reduction_reads_a_real_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((64, 64)).sum().block_until_ready()
    jax.profiler.stop_trace()
    out = trace_reduce.reduce_trace(str(tmp_path))
    assert out["window_s"] > 0 and out["chips"] == 0   # the CPU: no device
    assert trace_reduce.describe(trace_reduce.find_xplane(str(tmp_path)))


# -- shapes -----------------------------------------------------------------

def test_decode_step_bytes_against_hand_arithmetic():
    mistral = json.load(open(os.path.join(
        HERE, "configs", "mistral-7b", "config.json")))
    mixtral = json.load(open(os.path.join(
        HERE, "configs", "mixtral-8x7b", "config.json")))
    attn = 4096 * 4096 * 2 + 2 * 4096 * 1024        # q, o, k, v
    mlp = 3 * 4096 * 14336
    per_layer = attn + mlp + 2 * 4096
    assert shapes.layer_params(mistral) == {
        "attention": attn, "mlp": mlp, "norms": 8192}
    head = 4096 * 32000 + 4096
    assert shapes.weight_bytes_per_step(mistral) == 2 * (
        mistral["num_hidden_layers"] * per_layer + head)
    moe_layer = attn + 8 * mlp + 4096 * 8 + 2 * 4096
    assert shapes.weight_bytes_per_step(mixtral) == 2 * (
        mixtral["num_hidden_layers"] * moe_layer + head)
    # K and V, 8 heads of 128, bf16, per layer: 4 KB a token
    assert shapes.kv_bytes_per_token(mistral) == 16 * 4096
    assert shapes.decode_step_bytes(mistral, 1000.0, 4) == (
        shapes.weight_bytes_per_step(mistral) + 65536 * 1000) / 4
    res = shapes.resident_bytes(mistral, 1024, 64)
    assert res["kv_pages"] == 1024 * 64 * 65536
    meta = json.load(open(os.path.join(HERE, "configs", "mistral-7b",
                                       "meta.json")))
    assert meta["sizes"]["weights_bytes"] == res["weights"]


# -- BENCHMARK.json and the layout -----------------------------------------

def test_benchmark_json_names_units_and_files():
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in b[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names), names
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
    for m in b["per_layer"]:
        spec = readers.load_metric(m["name"], HERE)
        assert (spec["layer"], spec["unit"], spec["moves"]) == (
            m["layer"], m["unit"], m["moves"])
        # a list names cells that report what the entry moves; without a
        # list the entry is every such cell's (run.py `in_cell`)
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", ())) <= \
            set(moved.get("workloads", cells))
    used = set()
    for w in b["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        traffic.load_mix(w["traffic"], HERE)
        traffic.load_cell(w["name"], HERE)
        used.add(w["config"])
    assert used == {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("benchmark/") and os.path.isfile(path)
        meta = json.load(open(os.path.join(os.path.dirname(path),
                                           "meta.json")))
        assert meta["source"] == c["source"]
        assert sorted(meta["reduced"]) == sorted(c["reduced"])
        assert os.path.isdir(os.path.join(HERE, "configs",
                                          meta["rehearsal_config"]))
    for dirpath, _, files in os.walk(HERE):
        if "__pycache__" in dirpath or "/out" in dirpath:
            continue
        for f in files:
            if f.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_configs_differ_from_the_published_file_in_depth_only():
    published = {"mistral-7b": 32, "mixtral-8x7b": 32,
                 "mixtral-8x7b-tp4": 32}
    for name, depth in published.items():
        cfg = json.load(open(os.path.join(HERE, "configs", name,
                                          "config.json")))
        assert cfg["num_hidden_layers"] < depth
        assert (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["vocab_size"], cfg["max_position_embeddings"],
                cfg["rope_theta"]) == (4096, 14336, 32, 8, 32000, 32768, 1e6)
        if "mixtral" in name:
            assert (cfg["num_local_experts"],
                    cfg["num_experts_per_tok"]) == (8, 2)


# -- end to end on the CPU, and adding files only ---------------------------

REHEARSAL_SEED = 2**31 + 17


def run_rehearsal(root, workload, seconds="4"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(REHEARSAL_SEED), "--seconds",
         seconds, "--trace", "1", "--rehearsal"],
        env=env, capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@functools.lru_cache(maxsize=None)
def rehearsed(workload):
    """One rehearsal a cell for this file's tests: minutes on the CPU."""
    return run_rehearsal(ROOT, workload)


@pytest.mark.parametrize("workload", ["mistral-7b.decode-closed",
                                      "mistral-7b.chat-open"])
def test_rehearsal_end_to_end(workload):
    line = rehearsed(workload)
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    # an open request of 64-128 tokens ends inside a 4 s window; a closed
    # one of 384-512 need not on this CPU: that steps ran is what is held
    assert line["failed"] == 0
    if workload.endswith("-open"):
        assert line["attempted"] > 0
    assert line["metrics"]["host.resume_ms"]["value"] > 0
    assert line["metrics"]["warmup.compiles_in_window"]["value"] == 0
    assert "device.idle_share" not in line["metrics"]   # no CPU number


def load_context(path):
    """A traced run's `layer_context.json` (run.py leaves one in the run's
    directory) as `readers.evaluate`'s `ctx`."""
    with open(path) as f:
        ctx = json.load(f)
    return {k: tuple(v) if k in ("prom", "engine") else v
            for k, v in ctx.items()}


def test_the_fold_moves_no_value():
    """PR 48's 128 expressions and today's list on ONE rehearsal run's
    context (`layer_context.json`, which every traced run leaves): every
    former (cell, name) value equals, to the last digit, the value of the
    entry that reads it in that cell today. A CPU trace has no device
    plane, so the trace leaves read nothing on both sides here: that side
    is tests/test_benchmark_lists.py's made-up modules and the chip's."""
    import test_benchmark_lists as lists
    cell = "mistral-7b.decode-closed"
    rehearsed(cell)
    ctx = load_context(os.path.join(
        ROOT, "chiprun_out", "benchmark", cell, f"s{REHEARSAL_SEED}-t1",
        "layer_context.json"))
    read = 0
    for former in lists.FORMER["per_layer"]:
        was = readers.evaluate(former["expr"], ctx)
        for c in lists.cells_of(former):
            now, = lists.now_named(former, c)
            assert readers.evaluate(now["expr"], ctx) == was, \
                (former["name"], c, now["name"])
        read += was is not None
    assert read >= 60   # counters and client rows: most of the list


def test_a_cell_mix_metric_and_check_are_added_as_files_only(tmp_path):
    """A throw-away configuration, mix, layer metric and set-up check in a
    temporary copy: new files and BENCHMARK.json entries, no edit."""
    root = tmp_path / "repo"
    os.makedirs(root)
    shutil.copytree(HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "dynamo_tpu"), root / "dynamo_tpu")
    b = bench_json()
    bdir = root / "benchmark"
    shutil.copytree(bdir / "configs" / "mistral-7b", bdir / "configs" / "toy")
    mix = json.load(open(bdir / "traffic" / "decode-closed.json"))
    mix["name"] = "toy-closed"
    json.dump(mix, open(bdir / "traffic" / "toy-closed.json", "w"))
    json.dump({"clients": 4}, open(bdir / "cells" / "toy.toy-closed.json",
                                   "w"))
    json.dump({"name": "toy.steps", "layer": "scheduler", "unit": "count",
               "better": "higher", "moves": "output_tok_s",
               "reader": "engine", "expr": {"engine": "engine_steps"}},
              open(bdir / "layer_metrics" / "toy.steps.json", "w"))
    with open(bdir / "checks" / "toy_check.py", "w") as f:
        f.write("def applies(meta):\n    return True\n\n"
                "async def run(ctx):\n    return []\n")
    b["configs"].append({"name": "toy", "source": "none", "reduced": [],
                         "file": "benchmark/configs/toy/config.json",
                         "why": "throw-away"})
    b["workloads"].append({"name": "toy.toy-closed", "config": "toy",
                           "traffic": "toy-closed", "chips": 1,
                           "why": "throw-away"})
    b["per_layer"].append({"name": "toy.steps", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "output_tok_s"})
    json.dump(b, open(root / "BENCHMARK.json", "w"))
    line = run_rehearsal(str(root), "toy.toy-closed", "3")
    assert line["metrics"]["toy.steps"]["value"] > 0
