"""The Moonlight configuration, its cell, its metrics and its reference check
(PR 31): the files that `moonlight-16b-a3b.context-closed` added beside the
harness, held to the published values and to the program's own reference.
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

CELL = "moonlight-16b-a3b.context-closed"
# the catalog row's `config`, Moonlight-16B-A3B
# (https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json),
# written out here: the catalog is not part of the repo and is not read
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 50000,
    "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
SOURCE = ("https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
          "config.json")
# the accepted metrics whose lists name this cell since PR 54 (until then
# each was twinned for it under a `mla_` name of its own)
SHARED = {"moe.dropped_share", "moe.pad_share", "moe.experts_hit",
          "device.moe_kernel_share", "moe.window_experts_hit",
          "attn.kv_pad_share", "attn.kv_read_mb"}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_configuration_differs_from_the_published_file_in_depth_only():
    cfg = load("configs", "moonlight-16b-a3b", "config.json")
    meta = load("configs", "moonlight-16b-a3b", "meta.json")
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(meta["reduced"])
    # what the file has beyond the published keys is listed as assumed
    assert set(cfg) - set(PUBLISHED) == {"architectures", "torch_dtype"} \
        <= set(meta["assumed"])
    assert cfg["num_hidden_layers"] in (8, 9)        # never under 4 expert
    assert cfg["architectures"] == ["DeepseekV3ForCausalLM"]
    assert meta["source"] == SOURCE
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "moonlight"
    assert meta["serve"] == ["--max-slots", "8", "--num-pages", "1024"]


def test_the_sizes_are_the_arithmetic_of_the_file_beside_them():
    cfg = load("configs", "moonlight-16b-a3b", "config.json")
    sizes = load("configs", "moonlight-16b-a3b", "meta.json")["sizes"]
    h, v, heads = cfg["hidden_size"], cfg["vocab_size"], 16
    r, dn, dr, dv = 512, 128, 64, 128
    attn = h * heads * (dn + dr) + h * (r + dr) + r \
        + r * heads * (dn + dv) + heads * dv * h
    assert sizes["attention_params"] == attn == 13_763_072
    dense = attn + 3 * h * cfg["intermediate_size"] + 2 * h
    assert sizes["dense_layer_params"] == dense == 82_973_184
    routed = 64 * 3 * h * cfg["moe_intermediate_size"]
    shared = 3 * h * 2 * cfg["moe_intermediate_size"]
    expert = attn + 2 * h + h * 64 + 64 + routed + shared
    assert sizes["expert_layer_params"] == expert == 584_847_936
    n = cfg["num_hidden_layers"]
    params = dense + (n - 1) * expert + 2 * h * v + h
    assert sizes["params"] == params
    assert sizes["weights_bytes"] == 2 * params + 2 * 64 * (n - 1)
    assert sizes["kv_bytes_per_token"] == n * (r + dr) * 2
    assert sizes["kv_bytes_per_token_expanded"] \
        == n * heads * (dn + dr + dv) * 2
    assert sizes["kv_page_bytes"] == 64 * sizes["kv_bytes_per_token"]
    assert sizes["kv_pages_reserved_bytes"] == 1024 * sizes["kv_page_bytes"]
    assert sizes["kv_pages_filled_bytes_max"] == 8 * 64 \
        * sizes["kv_page_bytes"]
    # what a window step reads: everything resident but the embedding
    # table and the routed experts, + one routed expert a layer for each
    # expert a layer call touched (counted by the program), + latents
    fixed = sizes["weights_bytes"] - 2 * h * v - 2 * (n - 1) * routed
    assert sizes["decode_step_fixed_bytes"] == fixed
    per_hit = 2 * (n - 1) * routed // 64
    assert sizes["decode_step_bytes_per_expert_hit"] == per_hit
    # all 64 experts a layer: an upper bound, kept for reference only
    step = 2 * (dense + (n - 1) * expert + h + h * v)
    assert sizes["decode_step_weight_bytes_all_experts"] == step
    assert 0 <= fixed + 64 * per_hit - step <= 2 * 64 * (n - 1)  # f32 bias
    if n == 9:
        assert params == 5_432_847_360 and step == 10_194_606_080
        assert (fixed, per_hit) == (1_336_237_056, 138_412_032)
        assert sizes["kv_bytes_per_token"] == 10_368
    roofline = load("layer_metrics", "device.mla_window_roofline.json")
    weights, latents = roofline["expr"]["args"][1]["args"][0]["args"][0][
        "args"]
    assert weights["args"][0] == {"const": fixed}
    assert weights["args"][1]["args"][0] == {"const": per_hit}
    assert weights["args"][1]["args"][1] == load(
        "layer_metrics", "moe.window_experts_hit.json")["expr"]
    # slots a step x bytes a token, as attn.kv_read_mb has them
    assert latents == load("layer_metrics", "attn.kv_read_mb.json")[
        "expr"]["args"][0]
    assert str(step) not in json.dumps(roofline["expr"])
    # a quarter of one chip's memory is passed by the weights alone
    assert sizes["weights_bytes"] >= 0.25 * 16e9


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell, = (w for w in b["workloads"] if w["name"] == CELL)
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "moonlight-16b-a3b", "context-closed", 1)
    assert load("cells", CELL + ".json") == {"clients": 8}
    config, = (c for c in b["configs"] if c["name"] == "moonlight-16b-a3b")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == SOURCE
    assert len(config["why"]) <= 200 and len(cell["why"]) <= 200
    # by name, never by position: later PRs append, a benchmark PR folds
    listed = {m["name"]: m for m in b["per_layer"]}
    own = listed["device.mla_window_roofline"]
    assert own["workloads"] == [CELL] and own["layer"] == "device programs"
    # since PR 49 a cell is named in a list and never in a metric's name:
    # the MoE block's, the window's and the attention's readings in this
    # cell are the accepted entries', whose lists name it
    for name in SHARED:
        assert CELL in listed[name]["workloads"], name
        assert listed[name]["moves"] == "tpot_p50_ms"
    assert "workloads" not in listed["device.window_step_ms"]
    assert listed["attn.kv_pad_share"]["layer"] == "attention"
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)


def test_the_mix_is_the_issues():
    mix = traffic.load_mix("context-closed", HERE)
    assert (mix["kind"], mix["pool"], mix["order"], mix["set_seed"]) == (
        "closed", 1024, "fixed", 1)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 3073,
                                    "hi": 3584}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    # one admission bucket of the 8192-position ladder, and one live width
    ladder = [1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64, 96, 128]
    assert traffic.check_admission(mix, 64, ladder) == 64 \
        == mix["admission_pages"]
    assert -(-3073 // 64) > 48        # every live row is past the 48 bucket
    # the warm-up's own requests stay inside both, and the long holders'
    # last chunk lands in the 16 bucket beside 4 decode rows
    warm = mix["warmup"]
    assert warm["first_holder"]["prompt_tokens"] % 64 == 16
    assert 3072 < warm["first_holder"]["prompt_tokens"] \
        + warm["first_holder"]["max_tokens"] <= 4096
    assert 3072 < warm["stagger"]["total"] <= 4096
    assert warm["stagger"]["prompt_lo"] > 3072


PROM_0 = {"llm_engine_attn_kv_tokens_total": 1.0e6,
          "llm_engine_attn_kv_slots_total": 2.0e6,
          "llm_engine_steps_total": 100.0,
          "llm_engine_kv_bytes_per_token": 10368.0,
          "llm_engine_moe_routed_total": 1.0e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 2.0e5,
          "llm_engine_moe_experts_hit_total": 6400.0,
          "llm_engine_moe_layer_calls_total": 100.0,
          "llm_engine_moe_window_experts_hit_total": 350.0,
          "llm_engine_moe_window_layer_calls_total": 10.0}
PROM_1 = {"llm_engine_attn_kv_tokens_total": 25.0e6,
          "llm_engine_attn_kv_slots_total": 34.0e6,
          "llm_engine_steps_total": 1100.0,
          "llm_engine_kv_bytes_per_token": 10368.0,
          "llm_engine_moe_routed_total": 25.0e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 34.0e5,
          "llm_engine_moe_experts_hit_total": 6400.0 + 8000 * 59.0,
          "llm_engine_moe_layer_calls_total": 8100.0,
          "llm_engine_moe_window_experts_hit_total": 350.0 + 1280 * 35.0,
          "llm_engine_moe_window_layer_calls_total": 1290.0}
# fixed + 35 experts a layer call + 32 000 slots of 10 368 B, at 819 GB/s
STEP_BYTES = 1336237056 + 138412032 * 35 + 32e6 / 1000 * 10368


@pytest.mark.parametrize("name,want", [
    ("attn.kv_pad_share", 100 * (1 - 24e6 / 32e6)),
    ("attn.kv_read_mb", 32e6 / 1000 * 10368 / 1e6),
    # 6.51 GB / 819e9 = 7.95 ms against a 128 ms window of 8: 49.7 %
    ("device.mla_window_roofline", 100 * (STEP_BYTES / 819e9) / 0.016),
    ("device.window_step_ms", 16.0),
    ("device.moe_kernel_share", 100 * 1.2 / 3.0),
    ("moe.dropped_share", 0.0),
    ("moe.pad_share", 100 * (1 - 24e5 / 32e5)),
    ("moe.experts_hit", 59.0),
    ("moe.window_experts_hit", 35.0)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 8, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "all_ops": [("gmm.3", 0.5), ("fusion.7", 1.8),
                                 ("gmm", 0.7)],
                     "modules": {"jit_engine_decode_window_full": [0.128] * 5,
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


def test_each_check_applies_to_its_own_configuration_alone():
    mine = load_module("reference_logits_moonlight", "checks",
                       "reference_logits_moonlight.py")
    olmoe = load_module("reference_logits", "checks", "reference_logits.py")
    for name in os.listdir(os.path.join(HERE, "configs")):
        meta = load("configs", name, "meta.json")
        assert mine.applies(meta) == (name == "moonlight-16b-a3b"), name
        assert olmoe.applies(meta) == (name == "olmoe-1b-7b"), name
    assert mine.PROMPTS == (40, 1100, 3300)
    assert mine.shared().PROMPTS == mine.PROMPTS
    assert olmoe.PROMPTS == (40, 130, 250)     # its own copy is untouched


# what the chip read (TPU v5e, PR 31, tools/olmoe_reference_probe.py
# --prompt-seeds 4242,777,31337 --then-float8): three draws of the served
# path, then the reference with its weights rounded to float8
SOUND = [{"median": 0.05849790573120117, "p90": 0.3579726219177246,
          "largest": 2.3690881729125977},
         {"median": 0.07518815994262695, "p90": 0.5180364608764648,
          "largest": 2.623541831970215},
         {"median": 0.07770919799804688, "p90": 0.4009392738342285,
          "largest": 2.464681625366211}]
FLOAT8 = {"median": 1.1593880653381348, "p90": 2.321651744842529,
          "largest": 4.417677402496338}


def test_the_checks_limits_separate_the_chips_readings():
    """The comparison that decides `correct`, on recorded readings: every
    sound draw passes with room, the float8 reference fails BOTH limits
    with room, and `largest` (a maximum over flipped experts, 1.7x apart
    between the two) is reported and decides nothing."""
    mine = load_module("reference_logits_moonlight", "checks",
                       "reference_logits_moonlight.py")
    p90, median = mine.LIMITS["bfloat16"]
    for got in SOUND:
        assert mine.problems({**got, "dtype": "bfloat16"}) == []
        assert 2 * got["p90"] < p90 and 3 * got["median"] < median
    bad = mine.problems({**FLOAT8, "dtype": "bfloat16"})
    assert len(bad) == 2 and "90th" in bad[0] and "median" in bad[1]
    assert FLOAT8["p90"] > 2 * p90 and FLOAT8["median"] > 3 * median
    # the two statistics separate by 3x or more; the largest does not
    assert FLOAT8["p90"] > 3 * max(g["p90"] for g in SOUND)
    assert FLOAT8["largest"] < 3 * max(g["largest"] for g in SOUND)
    assert mine.problems({**SOUND[0], "largest": 50.0,
                          "dtype": "bfloat16"}) == []
    assert mine.problems({**SOUND[0], "p90": float("nan"),
                          "dtype": "bfloat16"}) != []
    # readings() is what measure() reports: the p90 of 432 values
    diffs = [i / 432 for i in range(432)]
    got = mine.readings(diffs)
    assert got["values"] == 432 and got["largest"] == diffs[-1]
    assert 0.89 < got["p90"] < 0.91 and 0.49 < got["median"] < 0.51


def test_the_two_copies_of_the_reference_give_identical_logits():
    """benchmark/reference/moonlight.py imports nothing from dynamo_tpu; it
    and dynamo_tpu/models/reference.py must not drift
    (tests/test_moonlight.py holds the same line from the program's side)."""
    import jax
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    hf = load("configs", "rehearsal-tiny-moonlight", "config.json")
    cfg = config_from_hf(hf)
    assert cfg.is_mla and cfg.first_dense_layers == 1
    mod = load_module("bench_ref_moonlight", "reference", "moonlight.py")
    with open(os.path.join(HERE, "reference", "moonlight.py")) as f:
        assert "dynamo_tpu" not in f.read().split('"""', 2)[2]
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
        params, tokens, hf, positions=rows, expert_block=6, head_block=3,
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
    assert 0 < metrics["attn.kv_pad_share"]["value"] < 100
    assert metrics["attn.kv_read_mb"]["value"] > 0
    assert "device.mla_window_roofline" not in metrics     # no CPU time
    assert metrics["moe.dropped_share"]["value"] == 0
    assert 1 <= metrics["moe.experts_hit"]["value"] <= 16
    assert 0 <= metrics["moe.pad_share"]["value"] < 100
    # six seconds on the CPU admit prompts and reach no decode window: the
    # window's own reading (0 / 0 layer calls) is left out, as the
    # roofline is; where one ran, it touches no more experts than a chunk
    hit = metrics.get("moe.window_experts_hit")
    assert hit is None or 1 <= hit["value"] \
        <= metrics["moe.experts_hit"]["value"]
    with open(os.path.join(ROOT, "chiprun_out", "benchmark", CELL,
                           f"s{2**31 + 17}-t1", "run.json")) as f:
        side = json.load(f)
    # nothing but the window being too short for a 3.5k-token request to
    # finish in: the reference check and the other set-up checks passed
    assert [p for p in side["problems"]
            if "no request finished" not in p] == [], side["problems"]
