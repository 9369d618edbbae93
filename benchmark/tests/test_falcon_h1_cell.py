"""The Falcon-H1-34B configuration, its cell, its metrics and its reference
check (PR 45): the files that `falcon-h1-34b.decode-closed` added beside the
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

CONFIG = "falcon-h1-34b"
CELL = "falcon-h1-34b.decode-closed"
SOURCE = ("https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
          "config.json")
# the catalog row's `config` (Falcon-H1-34B-Instruct), written out here:
# the catalog is not part of the repo and is not read
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}
REDUCED = {"num_hidden_layers": 6}
ADDED = {"architectures", "torch_dtype", "num_hidden_layers_published"}
# the accepted metrics whose lists name this cell since PR 54 (until then
# each was twinned for it under an `fh1_` name of its own); the gaps, the
# window step, the step periods, the chain and the host between two steps
# are every cell's (no `workloads` key)
SHARED = {"linattn.state_rw_mb", "linattn.chunk_token_share",
          "linattn.inplace_share", "linattn.flat_step_share",
          "attn.kv_read_mb", "attn.kv_pad_share"}
EVERY = {"device.window_step_ms", "stream.gap_mixed_share",
         "stream.gap_mixed_ms", "stream.gap_window_ms"}
OWN = {"device.fh1_window_roofline", "device.fh1_ssm_kernel_share",
       "device.fh1_ssm_step_roofline"}


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


def test_the_configuration_differs_in_depth_alone():
    cfg, meta = (load("configs", CONFIG, f) for f in ("config.json",
                                                      "meta.json"))
    differs = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert differs == set(REDUCED) == set(meta["reduced"])
    assert {k: cfg[k] for k in REDUCED} == REDUCED
    assert cfg["num_hidden_layers_published"] \
        == PUBLISHED["num_hidden_layers"]
    assert set(cfg) - set(PUBLISHED) == ADDED
    assert cfg["num_hidden_layers"] >= 4          # the floor; period 1
    assert "12 pipeline stages of 6 blocks" in meta["deployment"]
    assert "34 %" in meta["reduced"]["num_hidden_layers"]
    assert meta["source"] == SOURCE
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "falcon_h1"
    assert meta["serve"][:4] == ["--max-slots", "64", "--num-pages", "1024"]
    for key in ("architectures", "equations", "multipliers", "state",
                "weights", "tokenizer", "config_keys", "kv_pages",
                "sampling", "serve_flags"):
        assert key in meta["assumed"], key
    for flag in meta["serve"][4::2]:
        assert flag in meta["assumed"]["serve_flags"], flag


def test_the_sizes_are_model_configs_own_arithmetic():
    import jax
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf
    cfg = config_from_hf(load("configs", CONFIG, "config.json"), name=CONFIG)
    meta = load("configs", CONFIG, "meta.json")
    sizes = meta["sizes"]
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))

    def nbytes(tree):
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(tree))
    assert sizes["params"] == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == 6 * 430_120_032 + 2 * 1_336_934_400 + 5120
    assert sizes["block_params"] == 430_120_032 == sizes["mixer_params"] \
        + sizes["attention_params"] + sizes["mlp_params"] + 2 * 5120
    assert sizes["mixer_params"] == 5120 * 9248 + 4 * 5120 + 5120 + 96 \
        + 4096 + 4096 * 5120
    assert sizes["weights_bytes"] == nbytes(params)
    assert sizes["embed_bytes"] == nbytes(params["embed"]) \
        == nbytes(params["lm_head"]) == sizes["head_bytes"]
    assert sizes["decode_step_fixed_bytes"] \
        == sizes["weights_bytes"] - sizes["embed_bytes"]
    # the head's share of the weight bytes a step reads: 34 % here
    assert round(100 * sizes["head_bytes"]
                 / sizes["decode_step_fixed_bytes"]) == 34
    assert sizes["state_bytes_per_slot"] == cfg.state_bytes_per_slot() \
        == 6 * (32 * 128 * 256 * 4 + 3 * 5120 * 2)
    assert sizes["ssm_step_bytes_per_row"] == 2 * 6 * 32 * 128 * 256 * 4
    flags = dict(zip(meta["serve"][::2], meta["serve"][1::2]))
    slots = int(flags["--max-slots"]) + int(flags["--max-prefill-batch"])
    assert sizes["state_slots"] == slots == 64 + 3
    assert sizes["state_bytes_reserved"] \
        == slots * sizes["state_bytes_per_slot"]
    # chunk rows that ride one step fit the [64,64] plan's flat width
    from dynamo_tpu.ops.attention import compact_step
    rows = int(flags["--max-prefill-batch"])
    width, _ = compact_step(np.zeros((64, 64), np.int32))
    assert (64 - rows) + rows * 64 <= width
    assert sizes["kv_bytes_per_token"] == cfg.kv_bytes_per_token() == 12288
    assert sizes["kv_pages_reserved_bytes"] == 1024 * 64 * 12288
    assert sizes["resident_reserved_bytes"] == sizes["weights_bytes"] \
        + sizes["state_bytes_reserved"] + sizes["kv_pages_reserved_bytes"]
    # a quarter of one chip's memory is passed by the weights alone, and
    # the whole fits the chip
    assert 0.25 * 16e9 <= sizes["weights_bytes"]
    assert sizes["resident_reserved_bytes"] < 14e9
    # the rooflines' constants are these
    window = json.dumps(load("layer_metrics",
                             "device.fh1_window_roofline.json")["expr"])
    assert f'"const": {sizes["decode_step_fixed_bytes"]}' in window
    assert "llm_engine_linattn_window_state_bytes_total" in window
    assert "llm_engine_attn_kv_tokens_total" in window
    assert str(sizes["weights_bytes"]) not in window    # no embedding table
    kernel = json.dumps(load("layer_metrics",
                             "device.fh1_ssm_step_roofline.json")["expr"])
    assert f'"const": {sizes["ssm_step_bytes_per_row"] // 6}' in kernel
    state = cfg.state_leaves()["ssm_s"][0]
    assert sizes["ssm_step_bytes_per_row"] // 6 \
        == 2 * 4 * int(np.prod(state))


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = benchmark()
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-closed", 1)
    assert load("cells", CELL + ".json") == {"clients": 64}
    assert "34%" in cell["why"]
    config = by_name(b["configs"], CONFIG)
    assert config["reduced"] == list(REDUCED)
    assert config["source"] == SOURCE
    assert config["file"] == f"benchmark/configs/{CONFIG}/config.json"
    assert len(config["why"]) <= 200 and len(cell["why"]) <= 200
    mine = {name: by_name(b["per_layer"], name) for name in OWN | SHARED}
    for name in OWN:
        assert mine[name]["workloads"] == [CELL]
    # since PR 49 a cell is named in a list and never in a metric's name:
    # what this cell shares with others it reads under the accepted
    # entries, whose lists name it (PR 54 folded its `fh1_` stand-ins)
    for name in SHARED:
        assert CELL in mine[name]["workloads"], name
    for m in mine.values():
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in EVERY:
        assert "workloads" not in by_name(b["per_layer"], name)
    assert {mine[n]["layer"] for n in SHARED if n.startswith("linattn.")} \
        == {"linear attention and state"}
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)


def test_the_mix_is_the_accepted_one():
    mix = traffic.load_mix("decode-closed", HERE)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 129, "hi": 256}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    assert mix["admission_pages"] == 12


SLOT = 25_350_144
PROM_0 = {"llm_engine_attn_kv_slots_total": 2.0e6,
          "llm_engine_attn_kv_tokens_total": 1.0e6,
          "llm_engine_steps_total": 100.0,
          "llm_engine_kv_bytes_per_token": 12288.0,
          "llm_engine_period_seconds": 10.0,
          "llm_engine_linattn_tokens_total": 6000.0,
          "llm_engine_linattn_chunk_tokens_total": 3000.0,
          "llm_engine_linattn_inplace_updates_total": 2000.0,
          "llm_engine_linattn_state_bytes_total": 1.0e9,
          "llm_engine_linattn_steps_total": 10.0,
          "llm_engine_linattn_window_state_bytes_total": 5.0e8,
          "llm_engine_linattn_window_steps_total": 8.0,
          "llm_engine_linattn_flat_steps_total": 1.0,
          "llm_engine_gap_total": 1000.0,
          "llm_engine_gap_mixed_total": 300.0,
          "llm_engine_gap_mixed_seconds": 12.0,
          "llm_engine_gap_window_total": 50.0,
          "llm_engine_gap_window_seconds": 3.0,
          "llm_engine_host_exposed_between_seconds": 0.5}
PROM_1 = {"llm_engine_attn_kv_slots_total": 2.0e6 + 1000 * 49152,
          "llm_engine_attn_kv_tokens_total": 1.0e6 + 1000 * 30000,
          "llm_engine_steps_total": 1100.0,
          "llm_engine_kv_bytes_per_token": 12288.0,
          "llm_engine_period_seconds": 60.0,
          "llm_engine_linattn_tokens_total": 6000.0 + 6 * 90000,
          "llm_engine_linattn_chunk_tokens_total": 3000.0 + 6 * 36000,
          "llm_engine_linattn_inplace_updates_total": 2000.0 + 6 * 72000,
          "llm_engine_linattn_state_bytes_total": 1.0e9 + 1500 * 128 * SLOT,
          "llm_engine_linattn_steps_total": 1510.0,
          "llm_engine_linattn_window_state_bytes_total":
          5.0e8 + 800 * 128 * SLOT,
          "llm_engine_linattn_window_steps_total": 808.0,
          "llm_engine_linattn_flat_steps_total": 1.0 + 693,
          "llm_engine_gap_total": 1000.0 + 90000,
          "llm_engine_gap_mixed_total": 300.0 + 22500,
          "llm_engine_gap_mixed_seconds": 12.0 + 22500 * 0.044,
          "llm_engine_gap_window_total": 50.0 + 3600,
          "llm_engine_gap_window_seconds": 3.0 + 3600 * 0.060,
          "llm_engine_host_exposed_between_seconds": 0.5 + 1.3}
ENGINE_0 = {"mixed_steps": 40, "mixed_steps_chained": 30}
ENGINE_1 = {"mixed_steps": 40 + 400, "mixed_steps_chained": 30 + 330}
# fixed + 64 rows' state both ways + 30 000 context tokens of 12 288 B
STEP_BYTES = 7835320576 + 128 * SLOT + 30000 * 12288
# the kernel: 432 000 updates of 8 388 608 B in 50 s of the loop, while it
# holds the chip 0.6 s of every 4 s traced
KERNEL = (432000 * 8388608 / 50.0 / 819e9) / (0.2 * 3.0 / 4.0)


@pytest.mark.parametrize("name,want", [
    ("linattn.state_rw_mb", 128 * SLOT / 1e6),
    ("linattn.chunk_token_share", 40.0),
    ("linattn.inplace_share", 80.0),
    ("attn.kv_read_mb", 49152 * 12288 / 1e6),
    ("attn.kv_pad_share", 100 * (1 - 30000 / 49152)),
    ("device.window_step_ms", 20.0),
    # 11.45 GB / 819e9 = 14.0 ms against a 60 ms window of 3: 69.9 %
    ("device.fh1_window_roofline", 100 * (STEP_BYTES / 819e9) / 0.020),
    ("device.fh1_ssm_kernel_share", 20.0),
    ("device.fh1_ssm_step_roofline", 100 * KERNEL),
    ("stream.gap_mixed_share", 25.0),
    ("stream.gap_mixed_ms", 44.0),
    ("stream.gap_window_ms", 60.0),
    # 1500 steps of the state, 800 of them windows: 693 of 700 flat
    ("linattn.flat_step_share", 99.0),
    ("pipeline.mixed_chained_share", 82.5),
    ("host.exposed_between_ms", 1.3)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": (ENGINE_0, ENGINE_1),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 3, "chips": 1},
           "trace": {"busy_s": 3.0, "window_s": 4.0,
                     "all_ops": [("ssd_step_slots.3", 0.25),
                                 ("fusion.7", 2.4),
                                 ("ssd_step_slots", 0.35)],
                     "modules": {"jit_engine_decode_window_full": [0.060] * 5,
                                 "jit_engine_decode_window_w1": [0.02],
                                 "jit_engine_step": [0.07]}}}
    spec = readers.load_metric(name, HERE)
    assert readers.evaluate(spec["expr"], ctx) == pytest.approx(want)
    assert want < 100 or spec["unit"] != "%"
    # on a program without the counters (the parent commit) the reader
    # finds nothing, returns nothing, and does not raise
    empty = {"prom": ({}, {}), "engine": ({}, {}), "trace": {},
             "client": {}, "peak": {}, "run": {}}
    assert readers.evaluate(spec["expr"], empty) is None


def test_the_check_applies_to_its_own_configuration_alone():
    mine = load_module("reference_logits_falcon_h1", "checks",
                       "reference_logits_falcon_h1.py")
    for name in os.listdir(os.path.join(HERE, "configs")):
        assert mine.applies(load("configs", name, "meta.json")) \
            == (name == CONFIG), name
    assert mine.PROMPTS == (40, 136, 200, 248)
    assert mine.shared().PROMPTS == mine.PROMPTS
    mix = traffic.load_mix("decode-closed", HERE)["prompt_tokens"]
    inside = [mix["lo"] <= n <= mix["hi"] for n in mine.PROMPTS]
    assert inside == [False, True, True, True]
    # every holder's admission width is the cell's (513..768 tokens) while
    # the first lives, and the first is past 512 tokens from its start
    assert 512 < mine.FIRST_HOLDER[0] and sum(mine.FIRST_HOLDER) == 768
    assert mine.HOLDERS > 32 and mine.HOLDER_TOKENS[1] < mine.FIRST_HOLDER[1]


CHECK_READINGS = ("change", "ref_float8", "ref_no_ssm", "ref_bf16_state",
                  "ref_bf16_act")


@pytest.mark.parametrize("name", CHECK_READINGS)
def test_the_checks_limits_separate_the_chips_readings(name):
    """The comparison that decides `correct`, on the readings recorded
    beside it (LIMIT_READINGS: TPU v5e, PR 45, call 8), through
    `problems()` itself. The draw the check runs passes every limit with
    room. The float8 reference and the reference without the state-space
    branch fail BOTH limits on the log-probabilities with room. The
    reference with a bfloat16 state passes those (they cannot see it) and
    fails the limit on the state's first block, with room: the precision
    below the one `assumed.state` states comes out not `correct`. The
    reference with bfloat16 activations is the served path's own
    precision and passes, recorded as not seen."""
    mine = load_module("reference_logits_falcon_h1", "checks",
                       "reference_logits_falcon_h1.py")
    read = {**mine.LIMIT_READINGS, **mine.CONTROLS_NOT_SEEN}
    assert set(read) == {"change", *mine.CONTROLS} == set(CHECK_READINGS)

    def found(scale=1.0, state_scale=1.0):
        (p90, median, largest), (first, furthest) = read[name]
        return mine.problems({
            "p90": p90 * scale, "median": median * scale,
            "largest": largest, "dtype": "bfloat16",
            "state_first_p90": first * state_scale,
            "state_largest": furthest * state_scale})
    if name in ("change", "ref_bf16_act"):
        assert found() == [] == found(1.8, 1.8)
    elif name == "ref_bf16_state":
        assert len(found()) == 1 == len(found(1.8, 0.6))
        assert "first block" in found()[0]
        # the log-probabilities alone would have let it through
        assert found(1.0, 0.25) == []
    elif name == "ref_float8":
        assert len(found()) == 4 and len(found(0.2, 0.2)) == 4
    else:
        # without the state-space branch the first block's state is the
        # change's (its mixer reads the embedding alone); every later one is off
        assert len(found()) == 3 == len(found(0.2, 0.2))
    missing = {k: v for k, v in zip(("p90", "median", "largest"),
                                    read[name][0])}
    assert "the served state was not read" in mine.problems(
        {**missing, "dtype": "bfloat16"})


def test_the_checks_state_readings_are_of_the_first_block_and_of_all():
    mine = load_module("reference_logits_falcon_h1", "checks",
                       "reference_logits_falcon_h1.py")
    import numpy as np
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((2, 20, 4, 5)).astype(np.float32)
    served = ref.copy()
    served[0, :3] *= 1.01         # three heads of the first block, 1 % off
    served[1, 2] = 0.0            # one of the second, all of it
    dist = np.asarray(mine.state_distances(served, ref))
    assert dist.shape == (2, 20)
    want = np.zeros((2, 20))
    want[0, :3], want[1, 2] = 0.01, 1.0
    np.testing.assert_allclose(dist, want, atol=1e-6)
    got = mine.state_readings(dist.tolist())
    assert got["state_largest"] == pytest.approx(1.0)
    assert got["state_first_p90"] == pytest.approx(0.01, rel=1e-4)
    assert got["state_by_block"] == [0.0, 0.0]
    # the nearer of the two reference states is the one compared
    both = np.stack([ref + 1.0, ref])
    near = mine.nearest_state(ref, both, 7)
    assert near["state_fed"] == 8 and near["state_largest"] == 0.0
    assert mine.nearest_state(ref, both[::-1], 7)["state_fed"] == 7


def test_the_benchmarks_reference_is_the_programs_and_its_blocked_form():
    """benchmark/reference/falcon_h1.py against dynamo_tpu/models/
    reference.py on the rehearsal configuration (identical logits), and
    `forward_blocked`, which the chip runs, against both, the MLP and the
    head in blocks that do not divide their widths; each control moves
    the result."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    mod = load_module("bench_ref_falcon_h1", "reference", "falcon_h1.py")
    with open(os.path.join(HERE, "reference", "falcon_h1.py")) as f:
        assert "dynamo_tpu" not in f.read().split('"""', 2)[2]
    hf = load("configs", "rehearsal-tiny-falcon-h1", "config.json")
    cfg = config_from_hf(hf, "tiny")
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 60)
    ours = np.asarray(reference.forward(params, tokens,
                                        **reference.arch_kwargs(cfg)))
    np.testing.assert_array_equal(
        ours, np.asarray(mod.forward(params, tokens, hf)))
    rows = [0, 17, 59]
    want = np.asarray(jax.nn.log_softmax(ours, axis=-1))[rows]
    blocked = np.asarray(mod.forward_blocked(
        params, tokens, hf, positions=rows, mlp_block=40, vocab_block=100))
    np.testing.assert_allclose(blocked, want, atol=2e-5)
    low = jnp.dtype("float8_e4m3fn")
    for control, least in (
            (dict(state_dtype=jnp.dtype("bfloat16")), 1e-4),
            (dict(act_dtype=jnp.dtype("bfloat16")), 1e-3),
            (dict(without_ssm=True), 0.1),
            (dict(cast=lambda a: a.astype(low).astype(a.dtype)), 0.01)):
        moved = np.asarray(mod.forward_blocked(
            params, tokens, hf, positions=rows, **control))
        assert np.abs(moved - blocked).max() > least, control
