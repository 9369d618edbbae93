"""The Trinity-Mini configuration, its cell, its metrics and its reference
check (PR 40): the files that `trinity-mini.context-closed` added beside the
harness, held to the published values (a copy kept in the configuration's
own `meta.json`, not the catalog's path) and to the program's own
reference. Entries of BENCHMARK.json are found by NAME, not by position,
and nothing here says what OTHER metrics' lists may name: a later PR that
appends this cell to an accepted metric's list breaks no test of this file.
"""
import importlib.util
import inspect
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import readers, traffic   # noqa: E402

NAME = "trinity-mini"
CELL = NAME + ".context-closed"
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
CUT = {"num_hidden_layers", "num_dense_layers", "layer_types"}
# name -> layer. But for the roofline, whose constants are this cut's,
# each is an accepted metric whose list names this cell since PR 54 (it
# read each under an `afm_` name of its own until then); the window step,
# the step periods and the host loop are every cell's (no `workloads` key)
METRICS = {
    "attn.pools_kv_read_mb": "attention",
    "attn.window_read_share": "attention",
    "attn.pools_kv_pad_share": "attention",
    "kv.window_pages_held": "scheduler",
    "kv.window_pages_released": "scheduler",
    "device.afm_window_roofline": "device programs",
    "moe.experts_hit": "MoE dispatch",
    "moe.window_experts_hit": "MoE dispatch",
    "moe.pad_share": "MoE dispatch",
    "moe.dropped_share": "MoE dispatch",
    "device.moe_kernel_share": "MoE dispatch"}


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


def test_the_configuration_is_the_published_one_but_for_the_cut():
    cfg = load("configs", NAME, "config.json")
    meta = load("configs", NAME, "meta.json")
    published = meta["published"]
    assert len(published) == 32 and published["model_type"] == "afmoe"
    differs = {k for k, v in published.items()
               if cfg.get(k, "absent") != v}
    assert differs == CUT == set(meta["reduced"])
    # the lead (published layer 0) and ONE whole period (layers 4-7)
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["layer_types"] == [published["layer_types"][i]
                                  for i in (0, 4, 5, 6, 7)] \
        == ["sliding_attention"] * 4 + ["full_attention"]
    # no width is changed
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts", "num_experts_per_tok", "num_shared_experts",
                "vocab_size", "sliding_window", "route_scale"):
        assert cfg[key] == published[key], key
    # what the file has beyond the published keys is listed as assumed
    extra = set(cfg) - set(published)
    assert extra == {"architectures", "torch_dtype",
                     "num_hidden_layers_published"}
    assert all(k in meta["assumed"]["config_keys"] for k in extra)
    assert cfg["architectures"] == ["AfmoeForCausalLM"]
    assert cfg["num_hidden_layers_published"] == 32
    assert meta["source"] == SOURCE
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "trinity"
    assert meta["serve"] == ["--max-slots", "8", "--num-pages", "1024"]
    assert meta["rehearsal_config"] == "rehearsal-tiny-trinity"
    # the four code-sourced equations, one entry each
    for key in ("out_gate", "head_qk_norm", "rope", "four_norms", "router",
                "tokenizer", "weights", "sampling", "kv_pages",
                "prefix_reuse"):
        assert key in meta["assumed"], key
    assert "pipeline stages" in meta["deployment"]
    # the rehearsal's toy has the same keys and the same cut
    toy = load("configs", "rehearsal-tiny-trinity", "config.json")
    assert set(toy) == set(cfg) - {"num_hidden_layers_published"}
    assert all(toy[k] == cfg[k] for k in CUT | {"sliding_window"})


def test_the_sizes_are_the_arithmetic_of_the_file_beside_them():
    cfg = load("configs", NAME, "config.json")
    sizes = load("configs", NAME, "meta.json")["sizes"]
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, hkv, hd = 32, 4, 128
    # q, o and the output gate; k and v; the two head norms
    attn = 3 * h * heads * hd + 2 * h * hkv * hd + 2 * hd
    assert sizes["attention_params"] == attn == 27_263_232
    expert = 3 * h * cfg["moe_intermediate_size"]
    assert sizes["expert_params"] == sizes["shared_expert_params"] \
        == expert == 6_291_456
    router = h * 128 + 128
    expert_layer = attn + 4 * h + router + 128 * expert + expert
    assert sizes["expert_layer_params"] == expert_layer == 839_131_520
    lead = attn + 4 * h + 3 * h * cfg["intermediate_size"]
    assert sizes["lead_layer_params"] == lead == 65_020_160
    n_lead, n = cfg["num_dense_layers"], cfg["num_hidden_layers"]
    params = n_lead * lead + (n - n_lead) * expert_layer + 2 * h * v + h
    assert sizes["params"] == params == 4_241_534_720
    # every leaf bfloat16 but the selection biases, float32
    assert sizes["weights_bytes"] == 2 * params + 2 * (n - n_lead) * 128
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
    assert sizes["window_pages_per_sequence_max"] == per_seq == 41
    assert sizes["kv_pages_window"] == (8 + 8) * per_seq
    assert sizes["kv_pages_window_reserved_bytes"] \
        == sizes["kv_pages_window"] * 64 * window * row
    assert sizes["kv_window_filled_bytes_max"] == 8 * 34 * 64 * window * row
    assert sizes["kv_if_every_layer_held_every_page_bytes"] \
        == 8 * 4096 * n * row
    # what a window step reads: everything resident but the embedding
    # table and the routed experts, + one routed expert a layer for each
    # expert a layer call touched (counted by the program), + KV by kind
    fixed = sizes["weights_bytes"] - 2 * h * v \
        - 2 * (n - n_lead) * 128 * expert
    assert sizes["decode_step_fixed_bytes"] == fixed == 1_220_633_088
    per_hit = 2 * (n - n_lead) * expert
    assert sizes["decode_step_bytes_per_expert_hit"] == per_hit == 50_331_648
    roofline = load("layer_metrics", "device.afm_window_roofline.json")
    weights, kv = roofline["expr"]["args"][1]["args"][0]["args"][0]["args"]
    assert weights["args"][0] == {"const": fixed}
    assert weights["args"][1]["args"][0] == {"const": per_hit}
    assert weights["args"][1]["args"][1] == readers.load_metric(
        "moe.window_experts_hit", HERE)["expr"]
    # KV by kind, as attn.pools_kv_read_mb has it
    assert kv == readers.load_metric(
        "attn.pools_kv_read_mb", HERE)[
        "expr"]["args"][0]
    # but for the two constants it is Mellum's expression
    swa = json.dumps(load("layer_metrics",
                          "device.swa_window_roofline.json")["expr"])
    assert json.dumps(roofline["expr"]) == swa.replace(
        "966246912", str(fixed)).replace("148635648", str(per_hit))
    # a quarter of one chip's memory is passed by the weights alone, and
    # what is reserved fits the chip
    assert sizes["weights_bytes"] >= 0.25 * 16e9
    assert sizes["resident_reserved_bytes"] == sizes["weights_bytes"] \
        + sizes["kv_pages_full_reserved_bytes"] \
        + sizes["kv_pages_window_reserved_bytes"] < 13e9
    # the program's own description gives the same numbers
    sys.path.insert(0, ROOT)
    import jax
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf
    mc = config_from_hf(cfg, NAME)
    assert mc.kv_bytes_per_token() == sizes["kv_bytes_per_token_full"]
    assert mc.window_kv_bytes_per_token() \
        == sizes["kv_bytes_per_token_window"]
    assert (mc.num_cache_layers, mc.num_window_layers) == (full, window)
    assert mc.max_model_len == 131072 and mc.moe_dropless
    leaves = jax.tree.leaves(jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), mc)))
    assert sum(a.size for a in leaves) == params
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == sizes["weights_bytes"]


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
    for name, layer in METRICS.items():
        m = by_name(b["per_layer"], name)
        assert CELL in m["workloads"] and m["layer"] == layer
        assert m["moves"] == "tpot_p50_ms"
        spec = readers.load_metric(name, HERE)
        assert (spec["unit"], spec["better"], spec["layer"],
                spec["moves"]) == (m["unit"], m["better"], layer,
                                   m["moves"])
        assert m["source"] == ("device_trace" if spec["reader"] == "trace"
                               else "program_counter")
    assert by_name(b["per_layer"], "device.afm_window_roofline")[
        "workloads"] == [CELL]
    assert "workloads" not in by_name(b["per_layer"],
                                      "device.window_step_ms")
    # every metric this cell reports has its file
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)


def test_the_mix_is_the_accepted_file_unedited():
    mix = traffic.load_mix("context-closed", HERE)
    assert (mix["kind"], mix["pool"], mix["order"]) == ("closed", 1024,
                                                        "fixed")
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 3073,
                                    "hi": 3584}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    assert mix["admission_pages"] == 64
    sys.path.insert(0, ROOT)
    from dynamo_tpu.engine.scheduler import page_bucket_ladder
    assert traffic.check_admission(mix, 64, page_bucket_ladder(2048)) == 64


PROM_0 = {"llm_engine_attn_kv_tokens_total": 1.0e6,
          "llm_engine_attn_kv_slots_total": 2.0e6,
          "llm_engine_attn_kv_window_tokens_total": 0.4e6,
          "llm_engine_attn_kv_window_slots_total": 0.5e6,
          "llm_engine_steps_total": 100.0,
          "llm_engine_kv_bytes_per_token_full": 2048.0,
          "llm_engine_kv_bytes_per_token_window": 8192.0,
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
          "llm_engine_period_seconds": 2.5}
PROM_1 = {"llm_engine_attn_kv_tokens_total": 29.0e6,
          "llm_engine_attn_kv_slots_total": 34.768e6,
          "llm_engine_attn_kv_window_tokens_total": 15.4e6,
          "llm_engine_attn_kv_window_slots_total": 17.908e6,
          "llm_engine_steps_total": 1100.0,
          "llm_engine_kv_bytes_per_token_full": 2048.0,
          "llm_engine_kv_bytes_per_token_window": 8192.0,
          "llm_engine_kv_window_pages_held_sum_total": 1000.0 + 8000 * 33.5,
          "llm_engine_kv_window_rows_total": 8100.0,
          "llm_engine_kv_window_pages_released_total": 50.0 + 125.0,
          "llm_engine_moe_routed_total": 25.0e5,
          "llm_engine_moe_dropped_total": 0.0,
          "llm_engine_moe_expert_rows_total": 34.0e5,
          "llm_engine_moe_experts_hit_total": 6400.0 + 4000 * 120.0,
          "llm_engine_moe_layer_calls_total": 4100.0,
          "llm_engine_moe_window_experts_hit_total": 410.0 + 640 * 50.0,
          "llm_engine_moe_window_layer_calls_total": 650.0,
          "llm_engine_steps_mixed": 10.0 + 800,
          "llm_engine_window_steps_total": 40.0 + 1600,
          "llm_engine_period_mixed_seconds": 1.0 + 800 * 0.030,
          "llm_engine_period_decode_seconds": 1.0 + 1600 * 0.010,
          "llm_engine_period_seconds": 2.5 + 800 * 0.030 + 1600 * 0.010}
# a step's tables: 8 rows x 64 pages x 64 slots of 2048 B and 8 x 34 x 64
# of 8192 B
FULL, WIN = 32768.0, 17408.0
KV_BYTES = FULL * 2048 + WIN * 8192
STEP_BYTES = 1220633088 + 50331648 * 50 + KV_BYTES


@pytest.mark.parametrize("name,want", [
    ("attn.pools_kv_read_mb", KV_BYTES / 1e6),
    ("attn.window_read_share", 100 * 34 / 64),
    ("attn.pools_kv_pad_share", 100 * (1 - (28e6 * 2048 + 15e6 * 8192)
                                     / (32.768e6 * 2048 + 17.408e6 * 8192))),
    ("kv.window_pages_held", 33.5),
    ("kv.window_pages_released", 0.125),
    # 3.95 GB / 819e9 = 4.8 ms against a 64 ms window of 8: 60 %
    ("device.afm_window_roofline", 100 * (STEP_BYTES / 819e9) / 0.008),
    ("device.window_step_ms", 8.0),
    ("device.moe_kernel_share", 100 * 1.2 / 3.0),
    ("moe.dropped_share", 0.0),
    ("moe.pad_share", 100 * (1 - 24e5 / 32e5)),
    ("moe.experts_hit", 120.0),
    ("moe.window_experts_hit", 50.0),
    ("step.mixed_period_ms", 30.0),
    ("step.window_period_ms", 10.0),
    ("step.mixed_time_share", 100 * 24.0 / (24.0 + 16.0))])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 8, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "all_ops": [("gmm.3", 0.5), ("fusion.7", 1.8),
                                 ("gmm", 0.7)],
                     "modules": {"jit_engine_decode_window_full": [0.064] * 5,
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
    mine = load_module("reference_logits_trinity", "checks",
                       "reference_logits_trinity.py")
    others = [load_module(f"reference_logits{n}", "checks",
                          f"reference_logits{n}.py")
              for n in ("", "_moonlight", "_ling", "_mellum")]
    for name in os.listdir(os.path.join(HERE, "configs")):
        meta = load("configs", name, "meta.json")
        assert mine.applies(meta) == (name == NAME), name
        if name == NAME:
            assert not any(o.applies(meta) for o in others)
    # inside the window, just past it, the cell's own length; the long
    # ones hand back two pages of the window pool inside their tokens
    assert mine.PROMPTS == (40, 2200, 3315)
    assert len(mine.PROMPTS) * mine.N_TOKENS >= 300
    window = load("configs", NAME, "config.json")["sliding_window"]
    assert mine.PROMPTS[0] + mine.N_TOKENS < window < mine.PROMPTS[1]
    for prompt in mine.PROMPTS[1:]:
        first = lambda cached: max(0, cached - window + 1) // 64
        assert first(prompt) > 0        # the table starts mid-context
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
    assert mine.FILLER_EXTRA == {"logprobs": True}


def test_the_checks_pilot_spares_the_walk_up_the_table_widths():
    """The row that decodes while the first filler is prefilled: admitted
    at the cell's one page-table width, so that a step beside it takes
    that width from its first chunk on, prefilled by served_logprobs.py's
    own program, and inside its first page for as long as it may decode
    alone (a second live page would be a window program more)."""
    sys.path.insert(0, ROOT)
    from dynamo_tpu.engine.config import EngineConfig
    from dynamo_tpu.engine.scheduler import next_bucket, page_bucket_ladder
    mine = load_module("reference_logits_trinity", "checks",
                       "reference_logits_trinity.py")
    logprobs = load_module("served_logprobs", "checks", "served_logprobs.py")
    from dynamo_tpu.models.loader import config_from_hf
    ecfg = EngineConfig()
    page = ecfg.page_size
    served = config_from_hf(load("configs", NAME, "config.json"))
    ladder = page_bucket_ladder(-(-served.max_model_len // page))
    width = lambda tokens: next_bucket(-(-tokens // page), ladder)
    prompt, max_tokens = mine.PILOT
    mix = traffic.load_mix("context-closed", HERE)
    assert width(prompt + max_tokens) == mix["admission_pages"]
    for filler, more in mine.FILLERS + (mine.FILLER_NEXT,):
        assert width(filler + more) == mix["admission_pages"]
    chunk = lambda tokens: next_bucket(tokens, list(ecfg.prefill_buckets))
    assert chunk(prompt) == chunk(40) == 64     # served_logprobs.py's 40
    assert "prompt_tokens=40" in inspect.getsource(logprobs.run)
    # a window that holds the pilot ALONE reads the live pages of the
    # pilot: the first four start inside its first page (the fifth would
    # be a program more), and the first filler's request arrives in the
    # first; beside the filler the live width is the filler's
    assert prompt + 1 + 3 * ecfg.decode_steps <= page


def tiny_model():
    import jax
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf
    hf = dict(load("configs", "rehearsal-tiny-trinity", "config.json"),
              sliding_window=16)
    cfg = config_from_hf(hf)
    assert cfg.window_pool and cfg.layer_kinds() == ("swa",) * 4 + ("mha",)
    params = llama.init_params(jax.random.PRNGKey(7), cfg)
    tokens = np.random.default_rng(7).integers(0, cfg.vocab_size, 48)
    return hf, cfg, params, tokens


def test_the_two_copies_of_the_reference_give_identical_logits():
    """benchmark/reference/trinity.py imports nothing from dynamo_tpu; it
    and dynamo_tpu/models/reference.py must not drift (tests/test_trinity.py
    holds the same line from the program's side)."""
    import jax
    import numpy as np
    hf, cfg, params, tokens = tiny_model()
    from dynamo_tpu.models import reference
    mod = load_module("bench_ref_trinity", "reference", "trinity.py")
    with open(os.path.join(HERE, "reference", "trinity.py")) as f:
        body = f.read().split('"""', 2)[2]
    assert "dynamo_tpu" not in body and "import" in body
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
    # in a lower precision it is another function
    import jax.numpy as jnp
    low = np.asarray(mod.forward_blocked(
        params, tokens, hf, positions=rows,
        cast=lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)))
    assert np.abs(low - want).max() > 0.05


def test_rehearsal_of_the_new_cell():
    from test_harness import run_rehearsal
    line = run_rehearsal(ROOT, CELL, seconds="6")
    assert line["correct"] is False and line["rehearsal"] is True
    assert line["attempted"] >= 0 and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["warmup.compiles_in_window"]["value"] == 0
    assert 0 < metrics["attn.pools_kv_pad_share"]["value"] < 100
    assert metrics["attn.pools_kv_read_mb"]["value"] > 0
    assert 45 < metrics["attn.window_read_share"]["value"] < 60
    assert 20 <= metrics["kv.window_pages_held"]["value"] <= 41
    assert metrics["kv.window_pages_released"]["value"] > 0
    assert "device.afm_window_roofline" not in metrics     # no CPU time
    assert metrics["moe.dropped_share"]["value"] == 0
    assert 1 <= metrics["moe.experts_hit"]["value"] <= 16
    assert metrics["step.mixed_period_ms"]["value"] > 0
    with open(os.path.join(ROOT, "chiprun_out", "benchmark", CELL,
                           f"s{2**31 + 17}-t1", "run.json")) as f:
        side = json.load(f)
    # nothing but the window being too short for a 3.5k-token request to
    # finish in: the reference check and the other set-up checks passed
    assert [p for p in side["problems"]
            if "no request finished" not in p] == [], side["problems"]


def probe(mutation):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "olmoe_reference_probe.py"),
         "--config", NAME, "--rehearsal", "--float32", "--mutation",
         mutation],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mutation", ["none", "rotate-full", "no-out-gate"])
def test_the_check_sees_a_model_served_wrong(mutation):
    """The check itself on the CPU rehearsal (tiny widths, the cell's
    lengths; served in float32, so that rounding is out of the way and
    the check's float32 limits apply): it passes on the model as served,
    through mixed steps and windows with pages released in the span
    (read here: median 4.8e-7, p90 1.4e-6), and FAILS, by both limits,
    where the full layers are rotated (0.056 / 0.148) or the output gate
    is skipped."""
    got = probe(mutation)
    assert got["dtype"] == "float32" and got["values"] == 3456
    assert got["mixed_steps"] >= 64 and got["window_steps"] >= 16
    assert got["pages_released"] > 0
    if mutation == "none":
        assert got["passes"], got
        return
    assert not got["passes"] and len(got["problems"]) == 2, got
    p90, median = got["limits"]
    assert got["p90"] > 10 * p90 and got["median"] > 10 * median


# what the chip read: see RECORDED in the check's comment
def test_the_checks_limits_separate_the_chips_readings():
    """The comparison that decides `correct`, on recorded readings: every
    sound draw passes with room, the float8 reference fails at least one
    limit with room, `largest` is reported and decides nothing, and a span
    that was not made of mixed steps AND windows is refused whatever it
    read."""
    mine = load_module("reference_logits_trinity", "checks",
                       "reference_logits_trinity.py")
    p90, median = mine.LIMITS["bfloat16"]
    base = {**mine.RECORDED["span"], "dtype": "bfloat16"}
    for got in mine.RECORDED["sound"]:
        assert mine.problems({**base, **got}) == []
        assert 1.5 * got["p90"] < p90 and 1.5 * got["median"] < median
    low = mine.RECORDED["float8"]
    bad = mine.problems({**base, **low})
    assert bad and all("logprob - reference" in p for p in bad)
    assert low["p90"] > 1.5 * p90 or low["median"] > 1.5 * median
    sound = mine.RECORDED["sound"][0]
    assert mine.problems({**base, **sound, "largest": 50.0}) == []
    assert mine.problems({**base, **sound, "p90": float("nan")}) != []
    # one row at a time: no mixed step beside a neighbour's chunk
    for key in ("mixed_steps", "window_steps"):
        bad = mine.problems({**base, **sound, key: 3.0})
        assert len(bad) == 1 and key in bad[0]
    assert all(mine.RECORDED["span"][key] > 1.5 * least
               for key, least in mine.MIN_STEPS.items())
