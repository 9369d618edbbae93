"""The Ling-3.0-flash-VL configuration, its cell, its metrics and its
reference check (PR 33): the files that `ling-3.0-flash-vl.decode-closed`
added beside the harness, held to the published values, to `ModelConfig`'s
own arithmetic and to the program's own reference. Entries of
BENCHMARK.json are found BY NAME: a later PR appends behind them.
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

CONFIG = "ling-3.0-flash-vl"
CELL = "ling-3.0-flash-vl.decode-closed"
SOURCE = ("https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/"
          "config.json")
# the catalog row's `config` (Ling-3.0-flash-VL), written out here: the
# catalog is not part of the repo and is not read. The two per-layer
# lists are nested groups, copied whole
PUBLISHED = {
    "image_patch_token": 157157, "video_patch_token": 156909,
    "image_start_token": 157158, "video_start_token": 157160,
    "num_hidden_layers": 42, "hidden_size": 2560, "intermediate_size": 6144,
    "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8,
    "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000,
    "rms_norm_eps": 1e-06, "head_dim": 128, "vocab_size": 157184,
    "partial_rotary_factor": 0.5, "moe_router_enable_expert_bias": True,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "use_qk_norm": True, "score_function": "sigmoid",
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
    "linear_silu": True, "rotary_dim": 64, "use_mla_nope": False,
    "short_conv_kernel_size": 4, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "gated_attention_proj_granularity_type": "head_wise",
    "mtp_use_kda": False, "no_kda_lora": True, "use_kda_lora": False,
    "kda_safe_gate": True, "kda_lower_bound": -5, "norm_topk_prob": True,
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2}
REDUCED = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 39296}
ADDED = {"architectures", "model_type", "torch_dtype", "tie_word_embeddings",
         "num_experts_published", "expert_first", "vocab_size_published",
         "num_hidden_layers_published"}
# the accepted metrics whose lists name this cell since PR 54 (until then
# each was twinned for it under a `ling_` name of its own)
SHARED = {"moe.dropped_share", "moe.pad_share", "device.moe_kernel_share",
          "moe.window_experts_hit", "moe.experts_hit", "attn.kv_pad_share",
          "attn.kv_read_mb", "linattn.state_rw_mb",
          "linattn.chunk_token_share", "linattn.inplace_share",
          "linattn.flat_step_share"}
OWN = {"moe.share_held_share", "device.ling_window_roofline"}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(name, *parts):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def test_the_configuration_differs_in_depth_experts_held_and_vocabulary():
    cfg, meta = (load("configs", CONFIG, f) for f in ("config.json",
                                                      "meta.json"))
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(REDUCED) == set(meta["reduced"])
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    # the published counts and the deployment are stated beside the cuts
    assert (cfg["num_experts_published"], cfg["vocab_size_published"],
            cfg["num_hidden_layers_published"], cfg["expert_first"]) == (
        PUBLISHED["num_experts"], PUBLISHED["vocab_size"],
        PUBLISHED["num_hidden_layers"], 0)
    assert set(cfg) - set(PUBLISHED) == ADDED
    assert "4 chips share each layer" in meta["deployment"]
    # floors: a whole period and >= 4 layers after the leads, >= 8
    # experts, >= an eighth of the vocabulary; no width is cut
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] \
        >= max(4, cfg["layer_group_size"])
    assert cfg["num_experts"] >= 8 and cfg["num_experts"] * 4 \
        == cfg["num_experts_published"]
    assert cfg["vocab_size"] * 8 >= PUBLISHED["vocab_size"]
    # no clamp at a kept layer
    assert not any(cfg["expert_swiglu_limit_list"][:8]
                   + cfg["share_expert_swiglu_limit_list"][:8])
    assert meta["source"] == SOURCE
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "ling"
    assert meta["serve"][:4] == ["--max-slots", "64", "--num-pages", "1024"]
    for key in ("architectures", "layer_kinds", "kda_gates", "kda_safe_gate",
                "group_score", "mla_qk_norm", "mla_gate",
                "num_kv_heads_for_linear_attn", "mtp", "vision", "state",
                "weights", "mixed_token_budget", "max_prefill_chunk",
                "decode_steps", "max_prefill_batch"):
        assert key in meta["assumed"], key
    # every flag beyond ISSUE 33's four is explained under its own name
    flags = dict(zip(meta["serve"][::2], meta["serve"][1::2]))
    assert flags["--decode-steps"] == "3" \
        and meta["assumed"]["decode_steps"].startswith("3 ")
    assert flags["--max-prefill-batch"] == "3" \
        and meta["assumed"]["max_prefill_batch"].startswith("3 ")


def test_the_sizes_are_model_configs_own_arithmetic():
    import jax
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf
    cfg = config_from_hf(load("configs", CONFIG, "config.json"), name=CONFIG)
    sizes = load("configs", CONFIG, "meta.json")["sizes"]
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(params)

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))
    assert sizes["params"] == sum(int(np.prod(a.shape)) for a in leaves) \
        == 5_342_031_200
    assert sizes["weights_bytes"] == nbytes(params)
    routed = nbytes([{k: v[k] for k in llama.EXPERT_LEAVES}
                     for v in params.values()
                     if isinstance(v, dict) and "router" in v])
    assert sizes["routed_expert_bytes"] == routed \
        == 6 * 128 * 3 * 2560 * 768 * 2
    assert sizes["embed_bytes"] == nbytes(params["embed"])
    assert sizes["decode_step_fixed_bytes"] \
        == sizes["weights_bytes"] - routed - sizes["embed_bytes"]
    assert sizes["decode_step_bytes_per_expert_hit"] == routed // 128 \
        == 70_778_880
    assert sizes["kda_mixer_params"] == 5 * 2560 * 4096 + 4096 * 2560 \
        + 2560 * 32 + 4 * 12288 + 32 + 4096 + 128
    assert sizes["mla_mixer_params"] == 31_965_696 + 192 + 64
    assert sizes["state_bytes_per_slot"] == cfg.state_bytes_per_slot() \
        == 7 * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    serve = load("configs", CONFIG, "meta.json")["serve"]
    flags = dict(zip(serve[::2], serve[1::2]))
    slots = int(flags["--max-slots"]) + int(flags["--max-prefill-batch"])
    assert sizes["state_slots"] == slots == 64 + 3
    assert sizes["state_bytes_reserved"] \
        == slots * sizes["state_bytes_per_slot"]
    # chunk rows that ride one step fit the [64,64] plan's flat width
    from dynamo_tpu.ops.attention import compact_step
    rows = int(flags["--max-prefill-batch"])
    width, _ = compact_step(np.zeros((64, 64), np.int32))
    assert (64 - rows) + rows * 64 <= width < (64 - rows - 1) \
        + (rows + 1) * 64
    assert sizes["kv_bytes_per_token"] == cfg.kv_bytes_per_token() == 1152
    assert sizes["kv_pages_reserved_bytes"] == 1024 * 64 * 1152
    assert sizes["resident_reserved_bytes"] == sizes["weights_bytes"] \
        + slots * sizes["state_bytes_per_slot"] \
        + sizes["kv_pages_reserved_bytes"]
    # a quarter of one chip's memory is passed by the weights alone
    assert sizes["weights_bytes"] >= 0.25 * 16e9
    # the roofline's constants are these, and it counts experts TOUCHED
    roofline = load("layer_metrics", "device.ling_window_roofline.json")
    text = json.dumps(roofline["expr"])
    assert f'"const": {sizes["decode_step_fixed_bytes"]}' in text
    assert f'"const": {sizes["decode_step_bytes_per_expert_hit"]}' in text
    assert "llm_engine_moe_window_experts_hit_total" in text
    assert "llm_engine_linattn_window_state_bytes_total" in text
    assert str(routed) not in text


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = benchmark()
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-closed", 1)
    assert load("cells", CELL + ".json") == {"clients": 64}
    config = by_name(b["configs"], CONFIG)
    assert config["reduced"] == sorted(REDUCED, key=list(REDUCED).index)
    assert config["source"] == SOURCE
    assert config["file"] == f"benchmark/configs/{CONFIG}/config.json"
    assert len(config["why"]) <= 200 and len(cell["why"]) <= 200
    mine = {name: by_name(b["per_layer"], name) for name in OWN | SHARED}
    for name in OWN:
        assert mine[name]["workloads"] == [CELL]
    # since PR 49 a cell is named in a list and never in a metric's name:
    # what this cell shares with others it reads under the accepted
    # entries, whose lists name it (PR 54 folded its `ling_` stand-ins)
    for name in SHARED:
        assert CELL in mine[name]["workloads"], name
    assert "workloads" not in by_name(b["per_layer"],
                                      "device.window_step_ms")
    for m in mine.values():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert mine["linattn.state_rw_mb"]["layer"] \
        == mine["linattn.chunk_token_share"]["layer"] \
        == "linear attention and state"
    assert mine["device.ling_window_roofline"]["layer"] == "device programs"
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)


def test_the_mix_is_the_accepted_one():
    mix = traffic.load_mix("decode-closed", HERE)
    assert (mix["kind"], mix["pool"], mix["order"], mix["set_seed"]) == (
        "closed", 1024, "fixed", 1)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 129, "hi": 256}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    assert mix["admission_pages"] == 12


SLOT = 15_196_160
PROM_0 = {"llm_engine_attn_kv_slots_total": 2.0e6,
          "llm_engine_steps_total": 100.0,
          "llm_engine_kv_bytes_per_token": 1152.0,
          "llm_engine_moe_routed_total": 1.0e5,
          "llm_engine_moe_routed_absent_total": 3.0e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 2.0e6,
          "llm_engine_moe_window_experts_hit_total": 800.0,
          "llm_engine_moe_window_layer_calls_total": 10.0,
          "llm_engine_linattn_tokens_total": 7000.0,
          "llm_engine_linattn_chunk_tokens_total": 3000.0,
          "llm_engine_linattn_state_bytes_total": 1.0e9,
          "llm_engine_linattn_steps_total": 10.0,
          "llm_engine_linattn_window_state_bytes_total": 5.0e8,
          "llm_engine_linattn_window_steps_total": 8.0}
PROM_1 = {"llm_engine_attn_kv_slots_total": 2.0e6 + 1000 * 49152,
          "llm_engine_steps_total": 1100.0,
          "llm_engine_kv_bytes_per_token": 1152.0,
          "llm_engine_moe_routed_total": 1.0e5 + 2.5e5,
          "llm_engine_moe_routed_absent_total": 3.0e5 + 7.5e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 2.0e6 + 1.0e7,
          "llm_engine_moe_window_experts_hit_total": 800.0 + 4800 * 80.0,
          "llm_engine_moe_window_layer_calls_total": 4810.0,
          "llm_engine_linattn_tokens_total": 7000.0 + 7 * 90000,
          "llm_engine_linattn_chunk_tokens_total": 3000.0 + 7 * 36000,
          "llm_engine_linattn_state_bytes_total": 1.0e9 + 1500 * 128 * SLOT,
          "llm_engine_linattn_steps_total": 1510.0,
          "llm_engine_linattn_window_state_bytes_total":
          5.0e8 + 800 * 128 * SLOT,
          "llm_engine_linattn_window_steps_total": 808.0}
# fixed + 80 experts a layer call + 64 rows' state both ways + 49 152
# slots of 1152 B
STEP_BYTES = 1423234176 + 70778880 * 80 + 128 * SLOT + 49152 * 1152


@pytest.mark.parametrize("name,want", [
    ("linattn.state_rw_mb", 128 * SLOT / 1e6),
    ("linattn.chunk_token_share", 40.0),
    ("moe.share_held_share", 25.0),
    ("moe.window_experts_hit", 80.0),
    ("moe.dropped_share", 0.0),
    ("moe.pad_share", 100 * (1 - 2.5e5 / 1.0e7)),
    ("device.moe_kernel_share", 100 * 1.2 / 3.0),
    ("device.window_step_ms", 20.0),
    # 9.09 GB / 819e9 = 11.1 ms against a 160 ms window of 8: 55.5 %
    ("device.ling_window_roofline", 100 * (STEP_BYTES / 819e9) / 0.020)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 8, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "all_ops": [("gmm.3", 0.5), ("fusion.7", 1.8),
                                 ("gmm", 0.7)],
                     "modules": {"jit_engine_decode_window_full": [0.160] * 5,
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


def test_the_check_applies_to_its_own_configuration_alone():
    mine = load_module("reference_logits_ling", "checks",
                       "reference_logits_ling.py")
    for name in os.listdir(os.path.join(HERE, "configs")):
        assert mine.applies(load("configs", name, "meta.json")) \
            == (name == CONFIG), name
    assert mine.PROMPTS == (40, 200, 700)
    assert mine.shared().PROMPTS == mine.PROMPTS


def test_the_checks_limits_separate_the_chips_readings():
    """The comparison that decides `correct`, on the readings recorded
    beside it (LIMIT_READINGS: TPU v5e, PR 33): the one draw the check
    runs passes with room on both statistics, and BOTH controls, the
    reference with a bfloat16 state and the float8 reference, fail both
    limits with room, through `problems()` itself."""
    mine = load_module("reference_logits_ling", "checks",
                       "reference_logits_ling.py")
    p90, median = mine.LIMITS["bfloat16"]
    read = mine.LIMIT_READINGS
    keys = ("p90", "median", "largest")
    sound = dict(zip(keys, read["change"]))
    assert mine.problems({**sound, "dtype": "bfloat16"}) == []
    assert 1.2 * sound["p90"] < p90 and 1.2 * sound["median"] < median
    for control, room in (("ref_bf16_state", 1.2), ("ref_float8", 5)):
        got = dict(zip(keys, read[control]))
        bad = mine.problems({**got, "dtype": "bfloat16"})
        assert len(bad) == 2 and "90th" in bad[0] and "median" in bad[1]
        assert got["p90"] > room * p90 and got["median"] > room * median
    # the same prompts served alone read nearly the same; another draw of
    # the prompts reads past the limits, and no run serves one
    alone = {k: dict(zip(keys, v)) for k, v in read["alone"].items()}
    for k in ("4242@512", "4242@256"):
        assert mine.problems({**alone[k], "dtype": "bfloat16"}) == [], k
    assert mine.problems({**alone["777@512"], "dtype": "bfloat16"}) != []
    assert mine.shared().SEED == 4242 and mine.SEED is None
    assert mine.HOLDERS + 1 > 8      # a step of more rows than kda_mix's 8
    assert mine.problems({**sound, "largest": 50.0,
                          "dtype": "bfloat16"}) == []
    assert mine.problems({**sound, "p90": float("nan"),
                          "dtype": "bfloat16"}) != []
    diffs = [i / 432 for i in range(432)]
    got = mine.readings(diffs)
    assert got["values"] == 432 and got["largest"] == diffs[-1]
    assert 0.89 < got["p90"] < 0.91 and 0.49 < got["median"] < 0.51


def test_the_two_copies_of_the_reference_give_identical_logits():
    """benchmark/reference/ling.py imports nothing from dynamo_tpu; it and
    dynamo_tpu/models/reference.py must not drift."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    hf = load("configs", "rehearsal-tiny-ling", "config.json")
    cfg = config_from_hf(hf)
    assert cfg.layer_kinds().count("kda") == 7 and cfg.experts_held == 8
    mod = load_module("bench_ref_ling", "reference", "ling.py")
    with open(os.path.join(HERE, "reference", "ling.py")) as f:
        assert "dynamo_tpu" not in f.read().split('"""', 2)[2]
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, 48)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    theirs = np.asarray(mod.forward(params, tokens, hf))
    np.testing.assert_array_equal(ours, theirs)
    # the blocked form the chip runs is the same function, at the rows
    # asked for; a bfloat16 state is another function
    rows = [0, 20, 47]
    blocked = np.asarray(mod.forward_blocked(
        params, tokens, hf, positions=rows, expert_block=3, vocab_block=200))
    want = np.asarray(jax.nn.log_softmax(theirs, axis=-1))[rows]
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    rounded = np.asarray(mod.forward_blocked(
        params, tokens, hf, positions=rows, state_dtype=jnp.bfloat16))
    assert np.abs(rounded - want).max() > 1e-3
