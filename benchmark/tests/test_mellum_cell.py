"""The Mellum configuration, its cell, its metrics and its reference check
(PR 38): the files that `mellum2-12b-a2.5b.context-closed` added beside the
harness, held to the catalog row's values and to the program's own
reference. Entries of BENCHMARK.json are found by NAME, not by position.
"""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import readers, traffic   # noqa: E402

NAME = "mellum2-12b-a2.5b"
CELL = NAME + ".context-closed"
SOURCE = ("https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
          "main/config.json")
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's `config` (Mellum2-12B-A2.5B-Instruct), written out
# here: the catalog is not part of the repo and is not read
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
CUT = {"num_hidden_layers", "layer_types", "mlp_layer_types"}
METRICS = {
    "attn.pools_kv_read_mb": "attention",
    "attn.window_read_share": "attention",
    "attn.pools_kv_pad_share": "attention",
    "kv.window_pages_held": "scheduler",
    "kv.window_pages_released": "scheduler",
    "device.window_step_ms": "device programs",
    "device.swa_window_roofline": "device programs",
    "moe.experts_hit": "MoE dispatch",
    "moe.window_experts_hit": "MoE dispatch",
    "moe.pad_share": "MoE dispatch",
    "moe.dropped_share": "MoE dispatch",
    "device.moe_kernel_share": "MoE dispatch",
    "stream.gap_mixed_share": "scheduler",
    "stream.gap_mixed_ms": "scheduler",
    "stream.gap_window_ms": "scheduler"}
# what every engine exports has no list since PR 54 (the gaps between a
# stream's tokens, the window step; the step periods and the host between
# two steps since PR 49); the others' lists name this cell, which read
# each under a `swa_` name of its own until PR 54
EVERY = {"device.window_step_ms", "stream.gap_mixed_share",
         "stream.gap_mixed_ms", "stream.gap_window_ms"}
ITL = set()    # the gaps are terms of a stream's pace: tpot_p50_ms (PR 54)


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def test_the_configuration_is_the_catalog_rows_but_for_the_depth():
    cfg = load("configs", NAME, "config.json")
    meta = load("configs", NAME, "meta.json")
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == CUT == set(meta["reduced"])
    # the depth and the two per-layer lists cut to it: whole periods
    n = cfg["num_hidden_layers"]
    assert n in (8, 12) and n % 4 == 0
    assert cfg["layer_types"] == PERIOD * (n // 4) \
        == PUBLISHED["layer_types"][:n]
    assert cfg["mlp_layer_types"] == ["sparse"] * n
    # what the file has beyond the published keys is listed as assumed
    extra = set(cfg) - set(PUBLISHED)
    assert extra == {"architectures", "torch_dtype",
                     "num_hidden_layers_published"}
    assert all(k in meta["assumed"]["config_keys"] for k in extra)
    assert cfg["architectures"] == ["MellumForCausalLM"]
    assert cfg["num_hidden_layers_published"] == 28
    assert meta["source"] == SOURCE
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "mellum"
    assert meta["serve"] == ["--max-slots", "8", "--num-pages", "1024"]
    for key in ("architectures", "qk_norm", "mtp", "max_window_layers",
                "intermediate_size", "rope", "prefix_reuse"):
        assert key in meta["assumed"], key
    assert "pipeline stages" in meta["deployment"]


def test_the_sizes_are_the_arithmetic_of_the_file_beside_them():
    cfg = load("configs", NAME, "config.json")
    sizes = load("configs", NAME, "meta.json")["sizes"]
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, hkv, hd = 32, 4, 128
    attn = h * heads * hd + 2 * h * hkv * hd + heads * hd * h
    assert sizes["attention_params"] == attn == 21_233_664
    expert = 3 * h * cfg["moe_intermediate_size"]
    assert sizes["expert_params"] == expert == 6_193_152
    layer = attn + 2 * h + h * 64 + 64 * expert
    assert sizes["layer_params"] == layer == 417_747_456
    n = cfg["num_hidden_layers"]
    params = n * layer + 2 * h * v + h
    assert sizes["params"] == params
    assert sizes["weights_bytes"] == 2 * params
    full = cfg["layer_types"].count("full_attention")
    window = n - full
    assert (sizes["full_layers"], sizes["window_layers"]) == (full, window)
    row = 2 * hkv * hd * 2
    assert sizes["kv_bytes_per_token_layer"] == row == 2048
    assert sizes["kv_bytes_per_token_full"] == full * row
    assert sizes["kv_bytes_per_token_window"] == window * row
    assert sizes["kv_pages_full_reserved_bytes"] == 1024 * 64 * full * row
    # a sequence's most: ceil((window + the largest chunk) / page) + 1
    per_seq = -(-(cfg["sliding_window"] + 512) // 64) + 1
    assert sizes["window_pages_per_sequence_max"] == per_seq == 25
    assert sizes["kv_pages_window"] == (8 + 8) * per_seq
    assert sizes["kv_pages_window_reserved_bytes"] \
        == sizes["kv_pages_window"] * 64 * window * row
    assert sizes["kv_window_filled_bytes_max"] == 8 * 18 * 64 * window * row
    assert sizes["kv_if_every_layer_held_every_page_bytes"] \
        == 8 * 4096 * n * row
    # what a window step reads: everything resident but the embedding
    # table and the routed experts, + one routed expert a layer for each
    # expert a layer call touched (counted by the program), + KV by kind
    fixed = sizes["weights_bytes"] - 2 * h * v - 2 * n * 64 * expert
    assert sizes["decode_step_fixed_bytes"] == fixed
    per_hit = 2 * n * expert
    assert sizes["decode_step_bytes_per_expert_hit"] == per_hit
    if n == 12:
        assert params == 5_465_956_608
        assert (fixed, per_hit) == (966_246_912, 148_635_648)
    roofline = load("layer_metrics", "device.swa_window_roofline.json")
    weights, kv = roofline["expr"]["args"][1]["args"][0]["args"][0]["args"]
    assert weights["args"][0] == {"const": fixed}
    assert weights["args"][1]["args"][0] == {"const": per_hit}
    assert weights["args"][1]["args"][1] == readers.load_metric(
        "moe.window_experts_hit", HERE)["expr"]
    # KV by kind, as attn.pools_kv_read_mb has it
    assert kv == load("layer_metrics", "attn.pools_kv_read_mb.json")[
        "expr"]["args"][0]
    # a quarter of one chip's memory is passed by the weights alone, and
    # what is reserved fits the chip
    assert sizes["weights_bytes"] >= 0.25 * 16e9
    assert sizes["resident_reserved_bytes"] == sizes["weights_bytes"] \
        + sizes["kv_pages_full_reserved_bytes"] \
        + sizes["kv_pages_window_reserved_bytes"] < 13e9
    # the program's own description gives the same numbers
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models.loader import config_from_hf
    mc = config_from_hf(cfg)
    assert mc.kv_bytes_per_token() == sizes["kv_bytes_per_token_full"]
    assert mc.window_kv_bytes_per_token() \
        == sizes["kv_bytes_per_token_window"]
    assert (mc.num_cache_layers, mc.num_window_layers) == (full, window)
    assert mc.max_model_len == 131072 and mc.moe_dropless


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "context-closed", 1)
    assert load("cells", CELL + ".json") == {"clients": 8}
    config = by_name(b["configs"], NAME)
    assert set(config["reduced"]) == CUT
    assert config["source"] == SOURCE
    assert config["file"] == f"benchmark/configs/{NAME}/config.json"
    assert len(config["why"]) <= 200 and len(cell["why"]) <= 200
    assert not any(w["chips"] != 1 for w in b["workloads"])
    for name, layer in METRICS.items():
        m = by_name(b["per_layer"], name)
        assert m["moves"] == ("itl_p95_ms" if name in ITL
                              else "tpot_p50_ms")
        # in a list, or every cell's: never in a metric's name
        if name in EVERY:
            assert "workloads" not in m
        else:
            assert CELL in m["workloads"]
        assert m["layer"] == layer
        spec = readers.load_metric(name, HERE)
        assert (spec["unit"], spec["better"], spec["layer"]) \
            == (m["unit"], m["better"], layer)
        assert m["source"] == ("device_trace" if spec["reader"] == "trace"
                               else "program_counter")
    # the one expression with this cut's constants is this cell's alone;
    # the two-pool readings are Trinity's too
    assert by_name(b["per_layer"], "device.swa_window_roofline")[
        "workloads"] == [CELL]
    assert len(by_name(b["per_layer"], "attn.pools_kv_read_mb")[
        "workloads"]) == 2
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)


def test_the_mix_is_the_issues():
    mix = traffic.load_mix("context-closed", HERE)
    assert (mix["kind"], mix["pool"], mix["order"]) == ("closed", 1024,
                                                        "fixed")
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 3073,
                                    "hi": 3584}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    # the 64-page admission bucket is a rung of the 131072-position ladder
    sys.path.insert(0, ROOT)
    from dynamo_tpu.engine.scheduler import page_bucket_ladder
    assert traffic.check_admission(mix, 64, page_bucket_ladder(2048)) == 64


PROM_0 = {"llm_engine_attn_kv_tokens_total": 1.0e6,
          "llm_engine_attn_kv_slots_total": 2.0e6,
          "llm_engine_attn_kv_window_tokens_total": 0.4e6,
          "llm_engine_attn_kv_window_slots_total": 0.5e6,
          "llm_engine_steps_total": 100.0,
          "llm_engine_kv_bytes_per_token_full": 6144.0,
          "llm_engine_kv_bytes_per_token_window": 18432.0,
          "llm_engine_kv_window_pages_held_sum_total": 1000.0,
          "llm_engine_kv_window_rows_total": 100.0,
          "llm_engine_kv_window_pages_released_total": 50.0,
          "llm_engine_moe_routed_total": 1.0e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 2.0e5,
          "llm_engine_moe_experts_hit_total": 6400.0,
          "llm_engine_moe_layer_calls_total": 100.0,
          "llm_engine_moe_window_experts_hit_total": 410.0,
          "llm_engine_moe_window_layer_calls_total": 10.0,
          "llm_engine_steps_mixed": 10.0,
          "llm_engine_window_steps_total": 40.0,
          "llm_engine_period_mixed_seconds": 1.0,
          "llm_engine_period_decode_seconds": 1.0,
          "llm_engine_period_seconds": 2.5,
          "llm_engine_host_exposed_between_seconds": 0.5,
          "llm_engine_host_resume_seconds": 0.1,
          "llm_engine_host_emit_seconds": 0.2,
          "llm_engine_host_submit_seconds": 0.3,
          "llm_engine_gap_total": 1000.0,
          "llm_engine_gap_mixed_total": 500.0,
          "llm_engine_gap_mixed_seconds": 20.0,
          "llm_engine_gap_window_total": 400.0,
          "llm_engine_gap_window_seconds": 10.0}
PROM_1 = {"llm_engine_attn_kv_tokens_total": 29.0e6,
          "llm_engine_attn_kv_slots_total": 34.768e6,
          "llm_engine_attn_kv_window_tokens_total": 8.4e6,
          "llm_engine_attn_kv_window_slots_total": 9.716e6,
          "llm_engine_steps_total": 1100.0,
          "llm_engine_kv_bytes_per_token_full": 6144.0,
          "llm_engine_kv_bytes_per_token_window": 18432.0,
          "llm_engine_kv_window_pages_held_sum_total": 1000.0 + 8000 * 17.5,
          "llm_engine_kv_window_rows_total": 8100.0,
          "llm_engine_kv_window_pages_released_total": 50.0 + 125.0,
          "llm_engine_moe_routed_total": 25.0e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 34.0e5,
          "llm_engine_moe_experts_hit_total": 6400.0 + 12000 * 59.0,
          "llm_engine_moe_layer_calls_total": 12100.0,
          "llm_engine_moe_window_experts_hit_total": 410.0 + 1920 * 41.0,
          "llm_engine_moe_window_layer_calls_total": 1930.0,
          "llm_engine_steps_mixed": 10.0 + 800,
          "llm_engine_window_steps_total": 40.0 + 1600,
          "llm_engine_period_mixed_seconds": 1.0 + 800 * 0.036,
          "llm_engine_period_decode_seconds": 1.0 + 1600 * 0.016,
          "llm_engine_period_seconds": 2.5 + 800 * 0.036 + 1600 * 0.016,
          "llm_engine_host_exposed_between_seconds": 0.5 + 1000 * 0.0007,
          "llm_engine_host_resume_seconds": 0.1 + 1000 * 0.0001,
          "llm_engine_host_emit_seconds": 0.2 + 1000 * 0.0002,
          "llm_engine_host_submit_seconds": 0.3 + 1000 * 0.0003,
          "llm_engine_gap_total": 1000.0 + 20000,
          "llm_engine_gap_mixed_total": 500.0 + 6400,
          "llm_engine_gap_mixed_seconds": 20.0 + 6400 * 0.036,
          "llm_engine_gap_window_total": 400.0 + 12800,
          "llm_engine_gap_window_seconds": 10.0 + 12800 * 0.016}
# a step's tables: 8 rows x 64 pages x 64 slots of 6144 B and 8 x 18 x 64
# of 18 432 B
FULL, WIN = 32768.0, 9216.0
KV_BYTES = FULL * 6144 + WIN * 18432
STEP_BYTES = 966246912 + 148635648 * 41 + KV_BYTES


@pytest.mark.parametrize("name,want", [
    ("attn.pools_kv_read_mb", KV_BYTES / 1e6),
    ("attn.window_read_share", 100 * 18 / 64),
    ("attn.pools_kv_pad_share", 100 * (1 - (28e6 * 6144 + 8e6 * 18432)
                                     / (32.768e6 * 6144 + 9.216e6 * 18432))),
    ("kv.window_pages_held", 17.5),
    ("kv.window_pages_released", 0.125),
    # 7.43 GB / 819e9 = 9.07 ms against a 112 ms window of 8: 64.8 %
    ("device.swa_window_roofline", 100 * (STEP_BYTES / 819e9) / 0.014),
    ("device.window_step_ms", 14.0),
    ("device.moe_kernel_share", 100 * 1.2 / 3.0),
    ("moe.dropped_share", 0.0),
    ("moe.pad_share", 100 * (1 - 24e5 / 32e5)),
    ("moe.experts_hit", 59.0),
    ("moe.window_experts_hit", 41.0),
    ("step.mixed_period_ms", 36.0),
    ("step.window_period_ms", 16.0),
    ("step.mixed_time_share", 100 * 28.8 / (28.8 + 25.6)),
    ("host.exposed_between_ms", 0.7),
    ("host.resume_ms", 0.1),
    ("host.emit_ms", 0.2),
    ("host.submit_ms", 0.3),
    ("stream.gap_mixed_share", 32.0),
    ("stream.gap_mixed_ms", 36.0),
    ("stream.gap_window_ms", 16.0)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 8, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "all_ops": [("gmm.3", 0.5), ("fusion.7", 1.8),
                                 ("gmm", 0.7)],
                     "modules": {"jit_engine_decode_window_full": [0.112] * 5,
                                 "jit_engine_decode_window_w2": [0.05],
                                 "jit_engine_step": [0.03]}}}
    spec = readers.load_metric(name, HERE)
    assert readers.evaluate(spec["expr"], ctx) == pytest.approx(want)
    assert want < 100 or spec["unit"] != "%"
    # on a program without the counters (the parent commit) the reader
    # finds nothing, returns nothing, and does not raise
    empty = {"prom": ({}, {}), "engine": ({}, {}), "trace": {},
             "client": {}, "peak": {}, "run": {}}
    assert readers.evaluate(spec["expr"], empty) is None
    # the cell's own, or every cell's (no `workloads` key, since PR 49)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = by_name(json.load(f)["per_layer"], name)
    assert name in METRICS or "workloads" not in entry


def test_each_check_applies_to_its_own_configuration_alone():
    mine = load_module("reference_logits_mellum", "checks",
                       "reference_logits_mellum.py")
    others = [load_module(f"reference_logits_{n}", "checks",
                          f"reference_logits{n}.py")
              for n in ("", "_moonlight", "_ling")]
    for name in os.listdir(os.path.join(HERE, "configs")):
        meta = load("configs", name, "meta.json")
        assert mine.applies(meta) == (name == NAME), name
        if name == NAME:
            assert not any(o.applies(meta) for o in others)
    # about 40, 1100 and 3300 tokens, a few hundred compared tokens, the
    # long ones handing back two pages of the window pool inside theirs
    assert mine.PROMPTS == (40, 1140, 3315)
    assert len(mine.PROMPTS) * mine.N_TOKENS >= 300
    for prompt in mine.PROMPTS[1:]:
        first = lambda cached: max(0, cached - 1024 + 1) // 64
        assert first(prompt + mine.N_TOKENS - 1) == first(prompt) + 2
    # beside fillers of the cell's length and sampling, in the cell's one
    # admission bucket, whole chunks at every rung, every slot taken
    mix = traffic.load_mix("context-closed", HERE)
    band = mix["prompt_tokens"]
    assert len(mine.FILLERS) + len(mine.PROMPTS) == load(
        "cells", CELL + ".json")["clients"]
    for prompt, max_tokens in mine.FILLERS + (mine.FILLER_NEXT,):
        assert band["lo"] <= prompt <= band["hi"] and prompt % 256 == 0
        assert 3457 <= prompt + max_tokens <= 4096
    assert mine.SAMPLED == {k: mix["sampling"][0][k]
                            for k in ("temperature", "top_p")}


def test_the_two_copies_of_the_reference_give_identical_logits():
    """benchmark/reference/mellum.py imports nothing from dynamo_tpu; it
    and dynamo_tpu/models/reference.py must not drift (tests/test_mellum.py
    holds the same line from the program's side)."""
    import jax
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    hf = dict(load("configs", "rehearsal-tiny-mellum", "config.json"),
              sliding_window=16)
    hf["rope_parameters"] = json.loads(json.dumps(hf["rope_parameters"]))
    hf["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    cfg = config_from_hf(hf)
    assert cfg.window_pool and cfg.layer_kinds() == ("swa",) * 3 + ("mha",)
    mod = load_module("bench_ref_mellum", "reference", "mellum.py")
    with open(os.path.join(HERE, "reference", "mellum.py")) as f:
        body = f.read().split('"""', 2)[2]
    assert "dynamo_tpu" not in body and "import" in body
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, 48)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    theirs = np.asarray(mod.forward(params, tokens, hf))
    np.testing.assert_array_equal(ours, theirs)
    # the blocked form the chip runs is the same function, at the rows
    # asked for
    rows = [0, 20, 47]
    blocked = np.asarray(mod.forward_blocked(
        params, tokens, hf, positions=rows, expert_block=6,
        vocab_block=200))
    want = np.asarray(jax.nn.log_softmax(theirs, axis=-1))[rows]
    np.testing.assert_allclose(blocked, want, atol=2e-5)


def test_rehearsal_of_the_new_cell():
    from test_harness import run_rehearsal
    line = run_rehearsal(ROOT, CELL, seconds="6")
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["attempted"] >= 0 and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["warmup.compiles_in_window"]["value"] == 0
    assert 0 < metrics["attn.pools_kv_pad_share"]["value"] < 100
    assert metrics["attn.pools_kv_read_mb"]["value"] > 0
    assert 20 < metrics["attn.window_read_share"]["value"] < 45
    assert 10 <= metrics["kv.window_pages_held"]["value"] <= 25
    assert "device.swa_window_roofline" not in metrics     # no CPU time
    assert "attn.kv_read_mb" not in metrics
    assert metrics["moe.dropped_share"]["value"] == 0
    assert 1 <= metrics["moe.experts_hit"]["value"] <= 16
    with open(os.path.join(ROOT, "chiprun_out", "benchmark", CELL,
                           f"s{2**31 + 17}-t1", "run.json")) as f:
        side = json.load(f)
    # nothing but the window being too short for a 3.5k-token request to
    # finish in: the reference check and the other set-up checks passed
    assert [p for p in side["problems"]
            if "no request finished" not in p] == [], side["problems"]


# what the chip read (TPU v5e, PR 38's review round, call 6, the committed
# files: tools/olmoe_reference_probe.py --config mellum2-12b-a2.5b
# --prompt-seeds 4242,777,31337 --then-float8): three draws of the served
# path, the compared rows beside five fillers, then the reference with its
# weights rounded to float8
SPAN = {"mixed_steps": 129.0, "window_steps": 96.0, "pages_released": 127.0}
SOUND = [{"median": 0.018444538116455078, "p90": 0.07014541625976563,
          "largest": 0.27204132080078125},
         {"median": 0.02239084243774414, "p90": 0.07226386070251464,
          "largest": 0.3228015899658203},
         {"median": 0.02293992042541504, "p90": 0.07329764366149902,
          "largest": 0.38544797897338867}]
FLOAT8 = {"median": 0.16251683235168457, "p90": 0.42179231643676757,
          "largest": 0.9961142539978027}


def test_the_checks_limits_separate_the_chips_readings():
    """The comparison that decides `correct`, on recorded readings: every
    sound draw passes with room, the float8 reference fails BOTH limits
    with room, `largest` is reported and decides nothing, and a span that
    was not made of mixed steps AND windows is refused whatever it read."""
    mine = load_module("reference_logits_mellum", "checks",
                       "reference_logits_mellum.py")
    p90, median = mine.LIMITS["bfloat16"]
    base = {**SPAN, "dtype": "bfloat16"}
    for got in SOUND:
        assert mine.problems({**base, **got}) == []
        assert 2 * got["p90"] < p90 and 2 * got["median"] < median
    bad = mine.problems({**base, **FLOAT8})
    assert len(bad) == 2 and "90th" in bad[0] and "median" in bad[1]
    assert FLOAT8["p90"] > 2 * p90 and FLOAT8["median"] > 2 * median
    assert mine.problems({**base, **SOUND[0], "largest": 50.0}) == []
    assert mine.problems({**base, **SOUND[0], "p90": float("nan")}) != []
    # one row at a time: no mixed step beside a neighbour's chunk
    for key in ("mixed_steps", "window_steps"):
        bad = mine.problems({**base, **SOUND[0], key: 3.0})
        assert len(bad) == 1 and key in bad[0]
    assert all(SPAN[key] > 1.5 * least
               for key, least in mine.MIN_STEPS.items())
