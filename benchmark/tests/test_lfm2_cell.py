"""The LFM2-8B-A1B configuration, its cell, its metrics and its reference
check (PR 50): the files that `lfm2-8b-a1b.context-closed` added beside the
harness, held to the published values, to `ModelConfig`'s own arithmetic
and to the program's own reference. Entries of BENCHMARK.json are found BY
NAME: a later PR appends behind them.
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

CONFIG = "lfm2-8b-a1b"
CELL = "lfm2-8b-a1b.context-closed"
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
C, F = "conv", "full_attention"
# the catalog row's `config` (LFM2-8B-A1B), written out here: the catalog
# is not part of the repo and is not read
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [C, C, F, C, C, C, F, C, C, C, F, C, C, C, F, C, C, C, F,
                    C, C, F, C, C],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = {"num_hidden_layers": 14,
           "layer_types": PUBLISHED["layer_types"][:14]}
ADDED = {"architectures", "torch_dtype", "num_hidden_layers_published"}
# the accepted metrics whose lists name this cell since PR 54 (until then
# it read each through a `conv_` stand-in, a file that held `expr_of`);
# `attn.kv_pad_share` is new on it: ISSUE 50 named it, PR 50 had no room
SHARED = {"moe.experts_hit", "moe.pad_share", "moe.dropped_share",
          "device.moe_kernel_share", "moe.window_experts_hit",
          "attn.kv_read_mb", "attn.kv_pad_share", "linattn.state_rw_mb",
          "linattn.chunk_token_share"}
EVERY = {"device.window_step_ms", "attn.split_step_share",
         "stream.gap_mixed_share", "stream.gap_mixed_ms",
         "stream.gap_window_ms"}
OWN = {"device.shortconv_window_roofline", "device.shortconv_mixed_roofline"}


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
    return next(e for e in entries if e["name"] == name)


def test_the_configuration_differs_in_depth_and_the_kinds_list_alone():
    cfg, meta = (load("configs", CONFIG, f) for f in ("config.json",
                                                      "meta.json"))
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(REDUCED) == set(meta["reduced"])
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["num_hidden_layers_published"] \
        == PUBLISHED["num_hidden_layers"]
    assert set(cfg) - set(PUBLISHED) == ADDED
    assert meta["published"] == PUBLISHED and meta["source"] == SOURCE
    # whole periods behind the lead: C C | F C C C x 3
    assert cfg["layer_types"] == [C, C] + [F, C, C, C] * 3
    assert "two pipeline stages" in meta["deployment"]
    assert "1.7x" in meta["reduced"]["num_hidden_layers"]
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "lfm2"
    assert meta["serve"] == ["--max-slots", "8", "--num-pages", "1024"]
    assert meta["rehearsal_config"] == "rehearsal-tiny-lfm2"
    for key in ("architectures", "tie_word_embeddings", "non_expert_layers",
                "expert_block", "conv_bias", "weights", "tokenizer",
                "config_keys", "kv_pages", "state", "sampling",
                "serve_flags"):
        assert key in meta["assumed"], key
    tiny = load("configs", "rehearsal-tiny-lfm2", "config.json")
    assert tiny["layer_types"] == cfg["layer_types"]     # the same kinds
    assert tiny["num_dense_layers"] == 2 and tiny["num_experts"] > 8


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

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))
    assert sizes["params"] == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == 2 * 60_827_648 + 9 * 369_174_560 + 3 * 362_877_088 \
        + 134_217_728 + 2_048
    assert sizes["weights_bytes"] == nbytes(params)
    assert sizes["embed_bytes"] == nbytes(params["embed"])
    assert "lm_head" not in params                 # the table is the head
    routed = sum(nbytes(params[run][name]) for run in ("run0", "run1")
                 for name in ("w_gate", "w_up", "w_down"))
    assert sizes["routed_expert_bytes"] == routed
    # what the two rooflines count: everything but the routed experts,
    # and ONE expert in each of the 12 expert layers a touched expert
    assert sizes["decode_step_fixed_bytes"] == nbytes(params) - routed
    assert sizes["decode_step_bytes_per_expert_hit"] * 32 == routed
    assert sizes["kv_bytes_per_token"] == cfg.kv_bytes_per_token() == 6144
    assert sizes["state_bytes_per_slot"] == cfg.state_bytes_per_slot() \
        == 11 * 2 * 2048 * 2
    assert 0.25 * 16e9 <= sizes["weights_bytes"]
    assert sizes["resident_reserved_bytes"] < 14e9
    for name in OWN:
        text = json.dumps(load("layer_metrics", name + ".json")["expr"])
        assert f'"const": {sizes["decode_step_fixed_bytes"]}' in text
        assert f'"const": {sizes["decode_step_bytes_per_expert_hit"]}' \
            in text
        assert "llm_engine_kv_bytes_per_token" in text
        assert str(sizes["weights_bytes"]) not in text


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = benchmark()
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "context-closed", 1)
    assert load("cells", CELL + ".json") == {"clients": 8}
    assert "8.46 GB" in cell["why"] and "chunk edges" in cell["why"]
    config = by_name(b["configs"], CONFIG)
    assert config["reduced"] == list(REDUCED)
    assert config["source"] == SOURCE
    assert config["file"] == f"benchmark/configs/{CONFIG}/config.json"
    assert len(config["why"]) <= 200 and len(cell["why"]) <= 200
    mine = {name: by_name(b["per_layer"], name) for name in OWN | SHARED}
    for m in mine.values():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # nothing else of the benchmark names the cell: two expressions of
    # its own, and the accepted entries whose lists it is on
    assert {m["name"] for m in b["per_layer"]
            if CELL in m.get("workloads", ())} == OWN | SHARED
    for name in OWN:
        assert mine[name]["workloads"] == [CELL]
        assert (mine[name]["unit"], mine[name]["better"],
                mine[name]["source"], mine[name]["moves"]) == (
                    "%", "higher", "device_trace", "tpot_p50_ms")
    # what every engine exports has no list at all
    for name in EVERY:
        assert "workloads" not in by_name(b["per_layer"], name)
    # no file of the cell's stands in for another any more (PR 54)
    assert not [f for f in os.listdir(os.path.join(HERE, "layer_metrics"))
                if ".conv_" in f]
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)


def test_the_mix_is_the_accepted_one():
    mix = traffic.load_mix("context-closed", HERE)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 3073,
                                    "hi": 3584}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    assert mix["admission_pages"] == 64 and mix["order"] == "fixed"


FIXED, HIT, SLOT = 878438656, 264241152, 90112
PROM_0 = {"llm_engine_attn_kv_slots_total": 2.0e6,
          "llm_engine_attn_kv_tokens_total": 1.0e6,
          "llm_engine_steps_total": 100.0,
          "llm_engine_kv_bytes_per_token": 6144.0,
          "llm_engine_moe_experts_hit_total": 1000.0,
          "llm_engine_moe_layer_calls_total": 100.0,
          "llm_engine_moe_window_experts_hit_total": 400.0,
          "llm_engine_moe_window_layer_calls_total": 40.0,
          "llm_engine_linattn_tokens_total": 6000.0,
          "llm_engine_linattn_chunk_tokens_total": 3000.0,
          "llm_engine_linattn_state_bytes_total": 1.0e6,
          "llm_engine_linattn_steps_total": 10.0,
          "llm_engine_linattn_window_state_bytes_total": 5.0e5,
          "llm_engine_linattn_window_steps_total": 8.0}
PROM_1 = {"llm_engine_attn_kv_slots_total": 2.0e6 + 1000 * 32768,
          "llm_engine_attn_kv_tokens_total": 1.0e6 + 1000 * 28000,
          "llm_engine_steps_total": 1100.0,
          "llm_engine_kv_bytes_per_token": 6144.0,
          # 1400 mixed steps x 12 layer calls at 31 experts, 800 window
          # steps x 12 at 20
          "llm_engine_moe_experts_hit_total":
          1000.0 + 12 * (1400 * 31 + 800 * 20),
          "llm_engine_moe_layer_calls_total": 100.0 + 12 * 2200,
          "llm_engine_moe_window_experts_hit_total": 400.0 + 12 * 800 * 20,
          "llm_engine_moe_window_layer_calls_total": 40.0 + 12 * 800,
          "llm_engine_linattn_tokens_total": 6000.0 + 11 * 100000,
          "llm_engine_linattn_chunk_tokens_total": 3000.0 + 11 * 90000,
          "llm_engine_linattn_state_bytes_total":
          1.0e6 + 2200 * 16 * SLOT,
          "llm_engine_linattn_steps_total": 2210.0,
          "llm_engine_linattn_window_state_bytes_total":
          5.0e5 + 800 * 16 * SLOT,
          "llm_engine_linattn_window_steps_total": 808.0}
# fixed + the experts touched + 8 rows' tails both ways + the context
WINDOW_BYTES = FIXED + 20 * HIT + 16 * SLOT + 28000 * 6144
MIXED_BYTES = FIXED + 31 * HIT + 16 * SLOT + 32768 * 6144


@pytest.mark.parametrize("name,want", [
    ("moe.experts_hit", (1400 * 31 + 800 * 20) / 2200),
    ("moe.window_experts_hit", 20.0),
    ("device.window_step_ms", 10.0),
    ("attn.kv_read_mb", 32768 * 6144 / 1e6),
    ("linattn.state_rw_mb", 16 * SLOT / 1e6),
    ("linattn.chunk_token_share", 90.0),
    # 6.34 GB / 819e9 = 7.7 ms against a 10 ms window step: 77 %
    ("device.shortconv_window_roofline",
     100 * (WINDOW_BYTES / 819e9) / 0.010),
    # 9.27 GB / 819e9 = 11.3 ms against a 14 ms mixed step: 81 %
    ("device.shortconv_mixed_roofline",
     100 * (MIXED_BYTES / 819e9) / 0.014)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 8, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0, "all_ops": [],
                     "modules": {"jit_engine_decode_window_full": [0.080] * 5,
                                 "jit_engine_decode_window_w1": [0.02],
                                 "jit_engine_step": [0.014] * 7}}}
    spec = readers.load_metric(name, HERE)
    assert readers.evaluate(spec["expr"], ctx) == pytest.approx(want)
    assert want < 100 or spec["unit"] != "%"
    # on a program without the counters (the parent commit) the reader
    # finds nothing, returns nothing, and does not raise
    empty = {"prom": ({}, {}), "engine": ({}, {}), "trace": {},
             "client": {}, "peak": {}, "run": {}}
    assert readers.evaluate(spec["expr"], empty) is None


def test_the_check_applies_to_its_own_configuration_alone():
    mine = load_module("reference_logits_lfm2", "checks",
                       "reference_logits_lfm2.py")
    for name in os.listdir(os.path.join(HERE, "configs")):
        assert mine.applies(load("configs", name, "meta.json")) \
            == (name == CONFIG), name
    for name in os.listdir(os.path.join(HERE, "checks")):
        if name.startswith("reference_logits") and name.endswith(".py") \
                and "lfm2" not in name:
            other = load_module(name[:-3], "checks", name)
            assert not other.applies(load("configs", CONFIG, "meta.json"))
    # the third prompt's last chunk holds one token, and the last fed
    # position of its sequence is a chunk edge
    assert mine.PROMPTS == (40, 1140, 3329)
    assert mine.PROMPTS[-1] % 64 == 1
    assert (mine.PROMPTS[-1] + mine.N_TOKENS - 1) % 64 == 0
    mix = traffic.load_mix("context-closed", HERE)
    lo = mix["prompt_tokens"]["lo"] + mix["max_tokens"]["lo"]
    hi = mix["prompt_tokens"]["hi"] + mix["max_tokens"]["hi"]
    for n_prompt, n_out in (*mine.FILLERS, mine.FILLER_NEXT):
        assert lo <= n_prompt + n_out <= hi and n_prompt % 256 == 0
    assert set(mine.CONTROLS) == {"ref_float8", "ref_lost_tail",
                                  "ref_bias_in_weights", "ref_bf16_act"}
    # the tails no router precedes: the lead's two conv layers
    assert mine.lead_conv_layers(load("configs", CONFIG, "config.json")) == 2
    assert mine.lead_conv_layers(
        {"num_dense_layers": 0, "layer_types": [F, C, C]}) == 0


def _reading(check, logp, state, span=(141.0, 96.0)):
    return {"p90": logp[0], "median": logp[1], "largest": logp[2],
            "state_lead_largest": state[0], "state_median": state[1],
            "mixed_steps": span[0], "window_steps": span[1],
            "dtype": "bfloat16"}


def test_the_checks_limits_separate_the_chips_readings():
    """`problems` on the readings the limits rest on (the builder's chip
    runs of PR 50, recorded in the check): the change passes, each control
    that must fail fails at least one limit, the one that is the served
    path's own precision passes."""
    check = load_module("reference_logits_lfm2", "checks",
                        "reference_logits_lfm2.py")
    assert check.LIMIT_READINGS, "the chip's readings are recorded"
    for name, (logp, state) in check.LIMIT_READINGS.items():
        bad = check.problems(_reading(check, logp, state))
        assert bool(bad) == (name != "change"), (name, bad)
    for name, (logp, state) in check.CONTROLS_NOT_SEEN.items():
        assert check.problems(_reading(check, logp, state)) == [], name
    assert set(check.LIMIT_READINGS) | set(check.CONTROLS_NOT_SEEN) \
        == {"change", *check.CONTROLS}
    # every limit lies between the change's reading and the nearest
    # failing control's, with room on both sides: a tenth on the
    # log-probabilities (the bias in the weights reads 1.26 x and 1.40 x
    # the change), twice on the tails
    change = check.LIMIT_READINGS["change"]
    p90, median = check.LIMITS["bfloat16"]
    largest, middle = check.STATE_LIMITS["bfloat16"]
    near = check.LIMIT_READINGS["ref_bias_in_weights"]
    assert 1.1 * change[0][0] < p90 < near[0][0] / 1.1
    assert 1.1 * change[0][1] < median < near[0][1] / 1.1
    far = check.LIMIT_READINGS["ref_float8"]
    assert 2 * change[1][0] < largest < far[1][0] / 2
    assert 2 * change[1][1] < middle < far[1][1] / 2
    # the lost tail fails every limit, the state's by the widest margin
    lost = check.LIMIT_READINGS["ref_lost_tail"]
    assert len(check.problems(_reading(check, *lost))) == 4
    assert lost[1][0] > 50 * largest
    # a span that rode one kind of step alone is refused
    got = _reading(check, *change, span=(141.0, 0.0))
    assert any("window_steps" in p for p in check.problems(got))
    got = _reading(check, *change)
    del got["state_lead_largest"]
    assert check.problems(got) == ["the served state was not read"]


def test_the_benchmarks_reference_is_the_programs_and_its_blocked_form():
    """benchmark/reference/lfm2.py `forward` against dynamo_tpu/models/
    reference.py on the rehearsal configuration (identical logits and
    tails), `forward_blocked` against `forward` (the same arithmetic in
    blocks; the tails after n tokens and after one more), and each control
    moving what it says it moves."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    mod = load_module("bench_ref_lfm2", "reference", "lfm2.py")
    hf = load("configs", "rehearsal-tiny-lfm2", "config.json")
    cfg = config_from_hf(hf, "tiny")
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 200)
    tails = []
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    theirs = np.asarray(mod.forward(params, tokens, hf, tails))
    np.testing.assert_array_equal(ours, theirs)
    rows = [0, 63, 64, 65, 127, 128, 199]
    want = np.asarray(jax.nn.log_softmax(ours, axis=-1))[rows]
    logp, states = mod.forward_blocked(
        params, tokens, hf, positions=rows, expert_block=5, vocab_block=50,
        state_tokens=199)
    np.testing.assert_allclose(np.asarray(logp), want, atol=2e-5)
    assert states.shape == (2, 11, 2, cfg.hidden_size)
    np.testing.assert_allclose(np.asarray(states[1]),
                               np.stack([np.asarray(t) for t in tails]),
                               atol=3e-5)
    # after 199 tokens the tail is one row older: its newer row is the
    # older row of the tail after 200
    np.testing.assert_allclose(np.asarray(states[0][:, 1]),
                               np.asarray(states[1][:, 0]), atol=1e-6)

    def moved(rows_moved, **control):
        got, tail = mod.forward_blocked(
            params, tokens, hf, positions=rows, state_tokens=192,
            **control)
        return np.abs(np.asarray(got) - want).max(axis=1), np.asarray(tail)
    # a tail lost at 64-token edges: rows at an edge and one past it move
    # by a nat, rows before the first edge not at all; the slot's tail
    # after 192 = 3 x 64 tokens is zero, one token on it has one row
    diff, tail = moved(rows, reset_every=64)
    assert diff[0] < 1e-5 and diff[1] < 1e-5 and min(diff[2], diff[3]) > 0.1
    assert not tail[0].any() and not tail[1][:, 0].any() \
        and tail[1][:, 1].any()
    diff, _ = moved(rows, bias_in_weights=True)
    assert diff.min() > 1e-2
    rounded, _ = moved(rows, act_dtype=jnp.dtype("bfloat16"))
    low = jnp.dtype("float8_e4m3fn")
    diff, _ = moved(rows, cast=lambda a: a.astype(low).astype(a.dtype))
    assert diff.min() > 1e-2
    # rounded activations move every row, and less than float8 weights do
    assert rounded.min() > 1e-4 and np.median(rounded) < np.median(diff)


def test_the_state_distance_tells_a_token_apart():
    """`nearest_state`: the served slot against the reference's tails
    after n tokens or after n + 1, whichever lies nearer; the other lies a
    whole row apart."""
    import numpy as np
    check = load_module("reference_logits_lfm2", "checks",
                        "reference_logits_lfm2.py")
    rng = np.random.default_rng(0)
    g = rng.normal(size=(11, 3, 64))          # rows n - 2, n - 1, n
    both = np.stack([g[:, :2], g[:, 1:]])
    for fed in (0, 1):
        held = both[fed] * (1 + 1e-3 * rng.normal(size=(11, 2, 64)))
        got = check.nearest_state(held, both, 3456)
        assert got["state_fed"] == 3456 + fed
        assert got["state_lead_largest"] == max(got["state_by_layer"][:2])
        assert got["state_median"] < 2e-3 and len(
            got["state_by_layer"]) == 11
        assert min(check.state_distances(held, both[1 - fed])) > 0.5


@pytest.mark.skipif(not os.environ.get("LFM2_REHEARSAL"),
                    reason="~8 min on the CPU: set LFM2_REHEARSAL=1")
def test_the_cells_rehearsal_serves_and_its_check_passes_in_float32():
    """The cell's whole control flow on the CPU (`--rehearsal`; its line
    is never `correct` by construction), and the check's own reading of
    the tiny configuration served in float32, where its float32 limits
    apply: sound passes them, every control fails them."""
    import subprocess
    run = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "2147483999", "--seconds", "6", "--trace", "1",
         "--rehearsal"], cwd=ROOT, capture_output=True, text=True,
        timeout=1500)
    assert run.returncode == 0, run.stderr[-2000:]
    line = json.loads(run.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["failed"] == 0
    assert "moe.experts_hit" in line["metrics"]
    probe = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "olmoe_reference_probe.py"),
         "--config", CONFIG, "--rehearsal", "--float32", "--then-controls"],
        cwd=ROOT, capture_output=True, text=True, timeout=1500)
    lines = [json.loads(x) for x in probe.stdout.splitlines()
             if x.startswith("{")]
    assert [x["mutation"] for x in lines] == [
        "none", "ref_float8", "ref_lost_tail", "ref_bias_in_weights",
        "ref_bf16_act"]
    assert [x["passes"] for x in lines] == [True] + [False] * 4
