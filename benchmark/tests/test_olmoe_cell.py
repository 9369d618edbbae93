"""The OLMoE configuration, its cell, its metrics and its reference check
(PR 27): the files that `olmoe-1b-7b.decode-closed` added beside the
harness, held to the catalog and to the program's own reference.
"""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import readers   # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = ("https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/"
          "main/config.json")
# the catalog row's `config`, OLMoE-1B-7B-0125-Instruct (kept here too: the
# catalog is not part of the repo)
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def test_the_configuration_differs_from_the_published_file_in_depth_only():
    cfg = load("configs", "olmoe-1b-7b", "config.json")
    meta = load("configs", "olmoe-1b-7b", "meta.json")
    published = dict(PUBLISHED)
    # the source is the one recorded beside the configuration and in
    # BENCHMARK.json; the catalog is compared where it has the row (it is
    # not part of the repo, and this round's has none for OLMoE)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed, = (c for c in json.load(f)["configs"]
                   if c["name"] == "olmoe-1b-7b")
    assert meta["source"] == listed["source"] == SOURCE
    if os.path.isfile(CATALOG):
        rows = [json.loads(line) for line in open(CATALOG)]
        for row in rows:
            if row["name"] == "OLMoE-1B-7B-0125-Instruct":
                assert row["config"] == PUBLISHED
                assert meta["source"] == row["source_url"]
    differs = {k for k, v in published.items() if cfg.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(meta["reduced"])
    assert cfg["num_hidden_layers"] in (8, 10, 12)
    assert cfg["architectures"] == ["OlmoeForCausalLM"]
    assert meta["reference"]["module"] == "olmoe"
    # the sizes in meta.json are the arithmetic of the file beside it
    h, f, e, v = 2048, 1024, 64, 50304
    layer = 4 * h * h + 2 * h + 2 * h + h * e + e * 3 * h * f
    n = cfg["num_hidden_layers"]
    sizes = meta["sizes"]
    assert sizes["layer_params"] == layer == 419569664
    assert sizes["weights_bytes"] == 2 * (n * layer + 2 * h * v + h)
    assert sizes["kv_page_bytes"] == 2 * n * 16 * 128 * 64 * 2
    assert sizes["decode_step_weight_bytes"] == 2 * (n * layer + h + h * v)
    assert sizes["resident_filled_bytes_max"] >= 11e9
    roofline = load("layer_metrics", "device.moe_window_roofline.json")
    const = roofline["expr"]["args"][1]["args"][0]["args"][0]["const"]
    assert const == sizes["decode_step_weight_bytes"]


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"]
                if w["name"] == "olmoe-1b-7b.decode-closed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmoe-1b-7b", "decode-closed", 1)
    assert load("cells", "olmoe-1b-7b.decode-closed.json") == {"clients": 32}
    config = next(c for c in b["configs"] if c["name"] == "olmoe-1b-7b")
    assert config["reduced"] == ["num_hidden_layers"]
    mine = {m["name"]: m for m in b["per_layer"]
            if m["layer"] == "MoE dispatch"}
    assert set(mine) >= {"moe.dropped_share", "moe.pad_share",
                         "moe.experts_hit", "device.moe_window_roofline"}
    assert all(m["moves"] == "tpot_p50_ms" for m in mine.values())
    # the capacity dispatch (Mixtral) feeds this series alone; the other
    # expert cells were appended to these lists by PR 54's fold
    assert {"olmoe-1b-7b.decode-closed", "mixtral-8x7b.decode-closed"} \
        <= set(mine["moe.dropped_share"]["workloads"])
    assert "mixtral-8x7b.decode-closed" not in \
        mine["moe.pad_share"]["workloads"]
    assert mine["device.moe_window_roofline"]["workloads"] == [cell["name"]]
    # every per-layer metric without a `workloads` list is the new cell's
    # too: its file must be there to be read
    for m in b["per_layer"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            readers.load_metric(m["name"], HERE)


PROM_0 = {"llm_engine_moe_routed_total": 1000.0,
          "llm_engine_moe_dropped_total": 10.0,
          "llm_engine_moe_expert_rows_total": 4000.0,
          "llm_engine_moe_experts_hit_total": 300.0,
          "llm_engine_moe_layer_calls_total": 10.0}
PROM_1 = {"llm_engine_moe_routed_total": 9000.0,
          "llm_engine_moe_dropped_total": 210.0,
          "llm_engine_moe_expert_rows_total": 36000.0,
          "llm_engine_moe_experts_hit_total": 6600.0,
          "llm_engine_moe_layer_calls_total": 110.0}


@pytest.mark.parametrize("name,want", [
    ("moe.dropped_share", 100 * 200 / 8000),
    ("moe.pad_share", 100 * (1 - 8000 / 32000)),
    ("moe.experts_hit", 6300 / 100),
    # 8597442560 bytes / 819e9 = 10.4975 ms against a 160 ms window of 8
    ("device.moe_window_roofline", 100 * (8597442560 / 819e9) / 0.020),
    ("device.moe_kernel_share", 100 * 1.5 / 3.0)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 8, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "modules": {"jit_engine_decode_window_full": [0.16] * 5,
                                 "jit_engine_decode_window_w2": [0.05],
                                 "jit_engine_step": [0.03]},
                     "all_ops": [["%gmm.3 = custom-call", 1.0],
                                 ["%gmm.4 = custom-call", 0.5],
                                 ["%fusion.1", 1.5]]}}
    spec = readers.load_metric(name, HERE)
    assert readers.evaluate(spec["expr"], ctx) == pytest.approx(want)
    # on a program without the counters or the kernel (the parent commit)
    # the reader finds nothing, returns nothing, and does not raise
    empty = {"prom": ({}, {}), "engine": ({}, {}), "trace": {},
             "client": {}, "peak": {}, "run": {}}
    assert readers.evaluate(spec["expr"], empty) is None


def test_the_check_applies_only_where_the_configuration_asks():
    spec = importlib.util.spec_from_file_location(
        "reference_logits", os.path.join(HERE, "checks",
                                         "reference_logits.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for name in os.listdir(os.path.join(HERE, "configs")):
        meta = load("configs", name, "meta.json")
        assert mod.applies(meta) == (name == "olmoe-1b-7b"), name
    assert mod.token_id(" w123") == 123 and mod.token_id("w7 ") == 7


def test_the_two_copies_of_the_reference_give_identical_logits():
    """benchmark/reference/olmoe.py imports nothing from dynamo_tpu; it and
    dynamo_tpu/models/reference.py must not drift (tests/test_olmoe.py
    holds the same line from the program's side)."""
    import jax
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    hf = load("configs", "rehearsal-tiny-olmoe", "config.json")
    cfg = config_from_hf(hf)
    spec = importlib.util.spec_from_file_location(
        "bench_ref_olmoe", os.path.join(HERE, "reference", "olmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(HERE, "reference", "olmoe.py")) as f:
        assert "dynamo_tpu" not in f.read().split('"""', 2)[2]
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, 48)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    theirs = np.asarray(mod.forward(params, tokens, hf))
    np.testing.assert_array_equal(ours, theirs)
    # the blocked form the chip runs is the same function
    blocked = np.asarray(mod.forward_blocked(params, tokens, hf,
                                             expert_block=4))
    want = np.asarray(jax.nn.log_softmax(theirs, axis=-1))
    np.testing.assert_allclose(blocked, want, atol=2e-2)
    # ... and, from float32 weights, to rounding
    p32 = jax.tree.map(lambda a: a.astype("float32"), params)
    np.testing.assert_allclose(
        np.asarray(mod.forward_blocked(p32, tokens, hf, expert_block=4)),
        np.asarray(jax.nn.log_softmax(mod.forward(p32, tokens, hf), -1)),
        atol=2e-5)


def test_rehearsal_of_the_new_cell():
    from test_harness import run_rehearsal
    line = run_rehearsal(ROOT, "olmoe-1b-7b.decode-closed")
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["warmup.compiles_in_window"]["value"] == 0
    assert line["metrics"]["moe.dropped_share"]["value"] == 0
    assert 0 <= line["metrics"]["moe.pad_share"]["value"] < 100
    assert 1 <= line["metrics"]["moe.experts_hit"]["value"] <= 16
    assert "device.moe_window_roofline" not in line["metrics"]  # no CPU time
    with open(os.path.join(ROOT, "chiprun_out", "benchmark",
                           "olmoe-1b-7b.decode-closed",
                           f"s{2**31 + 17}-t1", "run.json")) as f:
        side = json.load(f)
    assert side["problems"] == [], side["problems"]   # the reference check
