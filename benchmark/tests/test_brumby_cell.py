"""The Brumby-14B-Base configuration, its cell, its metrics and its reference
check (PR 55): the files that `brumby-14b.decode-closed` added beside the
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

CONFIG = "brumby-14b"
CELL = "brumby-14b.decode-closed"
SOURCE = ("https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/"
          "config.json")
# the catalog row's `config` (Brumby-14B-Base), written out here: the
# catalog is not part of the repo and is not read
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 8}
ADDED = {"architectures", "torch_dtype", "num_hidden_layers_published"}
# what this cell reads of the accepted `linattn.*` metrics, whose lists
# only a benchmark PR may append to: a stand-in each (`expr_of`)
STAND_INS = {"linattn.retention_state_rw_mb": "linattn.state_rw_mb",
             "linattn.retention_chunk_token_share":
             "linattn.chunk_token_share",
             "linattn.retention_inplace_share": "linattn.inplace_share",
             "linattn.retention_flat_step_share": "linattn.flat_step_share",
             "stream.retention_itl_p95_ms": "stream.itl_p95_ms"}
EVERY = {"device.window_step_ms", "device.mixed_step_ms",
         "stream.gap_mixed_share", "stream.gap_window_ms",
         "attn.split_step_share"}
OWN = {"device.retention_step_roofline", "device.retention_kernel_share",
       "device.retention_window_roofline", "device.retention_mixed_roofline"}


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
    assert "5 pipeline stages of 8 layers" in meta["deployment"]
    assert "23 %" in meta["reduced"]["num_hidden_layers"]
    assert meta["source"] == SOURCE
    assert "reference" not in meta       # checks/reference_logits.py's key
    assert meta["reference_check"]["module"] == "brumby"
    assert meta["serve"][:4] == ["--max-slots", "16", "--num-pages", "1024"]
    for key in ("architectures", "degree", "gate", "gate_tensor_name",
                "rope_kept", "qk_norm_kept", "scale", "eps",
                "no_output_gate", "state_dtype", "features_held", "weights",
                "tokenizer", "config_keys", "kv_pages", "sampling",
                "serve_flags", "itl_p95_ms"):
        assert key in meta["assumed"], key
    for flag in meta["serve"][4::2]:
        assert flag in meta["assumed"]["serve_flags"], flag


def test_the_sizes_are_model_configs_own_arithmetic():
    import jax
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.loader import config_from_hf
    cfg = config_from_hf(load("configs", CONFIG, "config.json"), name=CONFIG)
    sizes = load("configs", CONFIG, "meta.json")["sizes"]
    params = jax.eval_shape(
        lambda: llama.init_params(jax.random.PRNGKey(0), cfg))
    leaves = jax.tree.leaves(params)
    assert sum(a.size for a in leaves) == sizes["params"]
    assert sum(a.size * a.dtype.itemsize for a in leaves) \
        == sizes["weights_bytes"]
    assert sizes["decode_step_fixed_bytes"] \
        == sizes["weights_bytes"] - sizes["embed_bytes"] == 6841481472
    assert cfg.num_cache_layers == 0 and cfg.kv_bytes_per_token() == 0
    assert cfg.retention_features == sizes["retention_features"] == 8320
    assert cfg.state_bytes_per_slot() == sizes["state_bytes_per_slot"] \
        == 8 * 8 * (128 * 8320 + 8320) * 4
    assert sizes["retention_step_bytes_per_update"] \
        == 2 * cfg.state_bytes_per_slot() // 8 == 68689920
    assert sizes["state_bytes_reserved"] == 18 * sizes["state_bytes_per_slot"]
    # what the deployment would hold fills the chip: 83 % of 16 GB
    assert 0.8 < sizes["resident_reserved_bytes"] / 16e9 < 0.9


def test_the_cell_and_its_metrics_are_entries_of_the_benchmark():
    b = benchmark()
    cell = by_name(b["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "decode-closed", 1)
    assert load("cells", CELL + ".json") == {"clients": 16}
    assert "says nothing of long contexts" in cell["why"]
    config = by_name(b["configs"], CONFIG)
    assert config["reduced"] == list(REDUCED)
    assert config["source"] == SOURCE
    assert config["file"] == f"benchmark/configs/{CONFIG}/config.json"
    assert len(config["why"]) <= 200 and len(cell["why"]) <= 200
    listed = [m["name"] for m in b["per_layer"]]
    stand_ins = {n: of for n, of in STAND_INS.items() if n in listed}
    assert set(STAND_INS) - set(stand_ins) <= {"stream.retention_itl_p95_ms"}
    mine = {name: by_name(b["per_layer"], name)
            for name in OWN | set(stand_ins)}
    assert len(mine) <= 10                        # ISSUE 55's allowance
    for name, m in mine.items():
        assert m["workloads"] == [CELL], name
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name, of in stand_ins.items():
        accepted = by_name(b["per_layer"], of)
        assert CELL not in accepted["workloads"], of
        assert load("layer_metrics", name + ".json")["expr_of"] == of
    # the tail is end to end here, or per layer under its stand-in: one
    in_list = CELL in by_name(b["end_to_end"], "itl_p95_ms")["workloads"]
    assert in_list != ("stream.retention_itl_p95_ms" in stand_ins)
    for name in EVERY:
        assert "workloads" not in by_name(b["per_layer"], name)
    assert {mine[n]["layer"] for n in mine if n.startswith("linattn.")} \
        == {"linear attention and state"}
    assert mine["device.retention_step_roofline"]["layer"] \
        == "linear attention and state"
    assert mine["device.retention_window_roofline"]["layer"] \
        == mine["device.retention_mixed_roofline"]["layer"] \
        == "device programs"
    for m in b["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            readers.load_metric(m["name"], HERE)
    print(f"per_layer: {len(b['per_layer'])} of 128, "
          f"{128 - len(b['per_layer'])} free")


def test_the_mix_is_the_accepted_one():
    mix = traffic.load_mix("decode-closed", HERE)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 129, "hi": 256}
    assert mix["max_tokens"] == {"dist": "uniform", "lo": 384, "hi": 512}
    assert mix["sampling"] == [{"weight": 1, "temperature": 0.7,
                                "top_p": 0.95}]
    assert mix["admission_pages"] == 12


SLOT = 274_759_680
PROM_0 = {"llm_engine_period_seconds": 10.0,
          "llm_engine_compact_steps_total": 5.0,
          "llm_engine_attn_split_steps_total": 0.0,
          "llm_engine_linattn_tokens_total": 8000.0,
          "llm_engine_linattn_chunk_tokens_total": 3000.0,
          "llm_engine_linattn_inplace_updates_total": 2000.0,
          "llm_engine_linattn_state_bytes_total": 1.0e9,
          "llm_engine_linattn_steps_total": 10.0,
          "llm_engine_linattn_window_state_bytes_total": 5.0e8,
          "llm_engine_linattn_window_steps_total": 8.0,
          "llm_engine_linattn_flat_steps_total": 1.0}
PROM_1 = {"llm_engine_period_seconds": 60.0,
          "llm_engine_compact_steps_total": 5.0 + 150,
          "llm_engine_attn_split_steps_total": 0.0,
          "llm_engine_linattn_tokens_total": 8000.0 + 8 * 30000,
          "llm_engine_linattn_chunk_tokens_total": 3000.0 + 8 * 9600,
          "llm_engine_linattn_inplace_updates_total": 2000.0 + 8 * 24000,
          "llm_engine_linattn_state_bytes_total": 1.0e9 + 2000 * 32 * SLOT,
          "llm_engine_linattn_steps_total": 2010.0,
          "llm_engine_linattn_window_state_bytes_total":
          5.0e8 + 1850 * 32 * SLOT,
          "llm_engine_linattn_window_steps_total": 1858.0,
          "llm_engine_linattn_flat_steps_total": 1.0 + 150}
# fixed + 16 rows' state both ways
STEP_BYTES = 6841481472 + 32 * SLOT
# the kernel: 192 000 updates of 68 689 920 B in 50 s of the loop, while
# it holds the chip 1.6 s of every 4 s traced
KERNEL = (192000 * 68689920 / 50.0 / 819e9) / (0.5 * 3.2 / 4.0)


@pytest.mark.parametrize("name,want", [
    ("linattn.retention_state_rw_mb", 32 * SLOT / 1e6),
    ("linattn.retention_chunk_token_share", 32.0),
    ("linattn.retention_inplace_share", 80.0),
    ("linattn.retention_flat_step_share", 100.0),
    ("attn.split_step_share", 0.0),
    ("device.window_step_ms", 25.0),
    # 15.63 GB / 819e9 = 19.1 ms against a 50 ms window of 2: 76.4 %
    ("device.retention_window_roofline",
     100 * (STEP_BYTES / 819e9) / 0.025),
    ("device.retention_mixed_roofline", 100 * (STEP_BYTES / 819e9) / 0.060),
    ("device.retention_kernel_share", 50.0),
    ("device.retention_step_roofline", 100 * KERNEL)])
def test_the_metric_files_evaluate_on_recorded_sources(name, want):
    ctx = {"prom": (PROM_0, PROM_1), "engine": ({}, {}),
           "peak": {"hbm_bytes_per_s": 819e9},
           "run": {"decode_steps": 2, "chips": 1},
           "trace": {"busy_s": 3.2, "window_s": 4.0,
                     "all_ops": [("retention_step_slots.8", 0.9),
                                 ("fusion.7", 1.6),
                                 ("retention_step_slots", 0.7)],
                     "modules": {"jit_engine_decode_window_full": [0.050] * 5,
                                 "jit_engine_decode_window_w1": [0.03],
                                 "jit_engine_step": [0.06]}}}
    spec = readers.load_metric(name, HERE)
    assert readers.evaluate(spec["expr"], ctx) == pytest.approx(want)
    assert want <= 100 or spec["unit"] != "%"
    # on a program without the counters (the parent commit) the reader
    # finds nothing, returns nothing, and does not raise
    empty = {"prom": ({}, {}), "engine": ({}, {}), "trace": {},
             "client": {}, "peak": {}, "run": {}}
    assert readers.evaluate(spec["expr"], empty) is None


def test_the_check_applies_to_its_own_configuration_alone():
    mine = load_module("reference_logits_brumby", "checks",
                       "reference_logits_brumby.py")
    for name in os.listdir(os.path.join(HERE, "configs")):
        assert mine.applies(load("configs", name, "meta.json")) \
            == (name == CONFIG), name
    for path in os.listdir(os.path.join(HERE, "checks")):
        if path.startswith("reference_logits") and "brumby" not in path:
            other = load_module(path[:-3], "checks", path)
            assert not other.applies(load("configs", CONFIG, "meta.json"))
    assert mine.PROMPTS == (40, 136, 200, 248) and mine.N_TOKENS == 128
    mix = traffic.load_mix("decode-closed", HERE)["prompt_tokens"]
    inside = [mix["lo"] <= n <= mix["hi"] for n in mine.PROMPTS]
    assert inside == [False, True, True, True]
    # the rows beside the compared prompts and the four themselves are the
    # cell's batch; every holder outlasts them (one mixed step a request,
    # then 128 steps) inside the cell's admission width
    assert mine.HOLDERS + len(mine.PROMPTS) == 16
    assert 512 < mine.FIRST_HOLDER[0] and sum(mine.FIRST_HOLDER) == 768
    steps = mine.HOLDERS + len(mine.PROMPTS) + mine.N_TOKENS
    assert steps < mine.HOLDER_TOKENS[1] < mine.FIRST_HOLDER[1]
    assert max(mine.PROMPTS) + mine.N_TOKENS <= 768


CHECK_READINGS = ("change", "ref_float8", "ref_gate_one", "ref_degree_one",
                  "ref_lost_state", "ref_bf16_state", "ref_bf16_act")


@pytest.mark.parametrize("name", CHECK_READINGS)
def test_the_checks_limits_separate_the_chips_readings(name):
    """The comparison that decides `correct`, on the readings recorded
    beside it (LIMIT_READINGS: TPU v5e, PR 55), through `problems()`
    itself. The draw the check runs passes every limit with room. The
    float8 reference, the gate of one, degree 1 and the state lost at
    every 16-token edge each fail at least one limit, with room; the
    reference with a bfloat16 state fails what LIMIT_READINGS says it
    fails; the reference with bfloat16 activations is the served path's
    own precision and passes, recorded as not seen."""
    mine = load_module("reference_logits_brumby", "checks",
                       "reference_logits_brumby.py")
    read = {**mine.LIMIT_READINGS, **mine.CONTROLS_NOT_SEEN}
    assert set(read) == {"change", *mine.CONTROLS} == set(CHECK_READINGS)

    def found(scale=1.0, state_scale=1.0):
        (p90, median, largest), (s, z) = read[name]
        return mine.problems({
            "p90": p90 * scale, "median": median * scale,
            "largest": largest, "dtype": "bfloat16",
            "state_s": s * state_scale, "state_z": z * state_scale})
    if name in ("change", *mine.CONTROLS_NOT_SEEN):
        assert found() == [] == found(1.5, 1.5)
    else:
        assert found() and found(0.7, 0.7), name
    missing = dict(zip(("p90", "median", "largest"), read[name][0]))
    assert "the served state was not read" in mine.problems(
        {**missing, "dtype": "bfloat16"})


def test_the_checks_state_readings_are_relative_to_a_heads_largest_entry():
    mine = load_module("reference_logits_brumby", "checks",
                       "reference_logits_brumby.py")
    import numpy as np
    rng = np.random.default_rng(0)
    ref = (rng.standard_normal((4, 6, 20)).astype(np.float32),
           rng.standard_normal((4, 20)).astype(np.float32))
    served = tuple(a.copy() for a in ref)
    at = np.unravel_index(np.abs(ref[0][2]).argmax(), ref[0][2].shape)
    served[0][2][at] *= 1.01      # head 2's largest entry, 1 % off
    served[1][3] = 0.0            # head 3's normaliser, all of it
    s, z = (np.asarray(d) for d in mine.state_distances(served, ref))
    np.testing.assert_allclose(s, [0, 0, 0.01, 0], atol=1e-6)
    np.testing.assert_allclose(z, [0, 0, 0, 1.0], atol=1e-6)
    # the nearer of the two reference states is the one compared
    both = tuple(np.stack([a + 1.0, a]) for a in ref)
    near = mine.nearest_state(ref, both, 7)
    assert near["state_fed"] == 8 and near["state_s"] == 0.0 \
        == near["state_z"]
    flipped = tuple(a[::-1] for a in both)
    assert mine.nearest_state(ref, flipped, 7)["state_fed"] == 7


def test_the_benchmarks_reference_is_the_programs_and_its_blocked_form():
    """benchmark/reference/brumby.py against dynamo_tpu/models/
    reference.py on the rehearsal configuration (identical logits), and
    `forward_blocked`, which the chip runs, against both, the MLP and the
    head in blocks that do not divide their widths; the recurrence gives
    the quadratic form's outputs and the program's own state; each
    control moves the result."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sys.path.insert(0, ROOT)
    from dynamo_tpu.models import llama, reference
    from dynamo_tpu.models.loader import config_from_hf
    mod = load_module("bench_ref_brumby", "reference", "brumby.py")
    with open(os.path.join(HERE, "reference", "brumby.py")) as f:
        assert "dynamo_tpu" not in f.read().split('"""', 2)[2]
    hf = load("configs", "rehearsal-tiny-brumby", "config.json")
    cfg = config_from_hf(hf, "tiny")
    params = llama.init_params(jax.random.PRNGKey(5), cfg)
    tokens = np.random.default_rng(5).integers(0, cfg.vocab_size, 60)
    tails = []
    ours = np.asarray(reference.forward(
        params, tokens, **reference.arch_kwargs(cfg), tails=tails))
    np.testing.assert_array_equal(
        ours, np.asarray(mod.forward(params, tokens, hf)))
    rows = [0, 17, 59]
    want = np.asarray(jax.nn.log_softmax(ours, axis=-1))[rows]
    blocked, (s, z) = mod.forward_blocked(
        params, tokens, hf, positions=rows, mlp_block=40, vocab_block=100,
        state_tokens=59)
    np.testing.assert_allclose(np.asarray(blocked), want, atol=2e-5)
    # after 59 tokens and after all 60: the second is the program's own
    assert s.shape == (2, 2, 32, 544) and z.shape == (2, 2, 544)
    np.testing.assert_allclose(np.asarray(s[1]), tails[0][0], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(z[1]), tails[0][1], rtol=1e-4,
                               atol=1e-5)
    assert np.abs(np.asarray(s[0] - s[1])).max() > 1e-2
    low = jnp.dtype("float8_e4m3fn")
    bf16 = jnp.dtype("bfloat16")
    # the recurrence (what a rounded state is read through) at float32 IS
    # the quadratic form: a bfloat16 state then moves it
    for control, least in (
            (dict(state_dtype=bf16), 1e-4), (dict(act_dtype=bf16), 1e-3),
            (dict(gate_one=True), 0.01), (dict(degree=1), 0.01),
            (dict(reset_every=16), 0.01),
            (dict(cast=lambda a: a.astype(low).astype(a.dtype)), 0.01)):
        moved = np.asarray(mod.forward_blocked(
            params, tokens, hf, positions=rows, **control))
        assert np.abs(moved - np.asarray(blocked)).max() > least, control
    x = jnp.asarray(np.random.default_rng(1).normal(size=(40, 64)),
                    jnp.float32)
    lp = {k: jnp.asarray(v[0], jnp.float32)
          for k, v in params["layers"].items()}
    sizes = mod.arch_from_hf(hf)
    q, k, v, log_g = mod.retention_inputs(x, lp, **sizes)
    _, by_token = mod.retention_recurrent(q, k, v, log_g)
    # phi(q) . phi(k) cancels to (q . k)^2: where a token's own weight is
    # small beside |q|^2 |k|^2 (a sequence's first tokens) the quotient's
    # float32 error grows with that ratio, so the bulk is held, not the
    # largest
    apart = np.abs(np.asarray(by_token) - np.asarray(
        mod.retention_quadratic(q, k, v, log_g)))
    assert np.quantile(apart, 0.99) < 2e-4 and np.median(apart) < 2e-6
