"""`engine/config.refuse_unserved`, walked row by row.

`UNSERVED` is the one table of what a kind of cache is not served with:
a row a consumer, a column a store. Every (store, consumer) pair that has
a reason raises under the store's opening sentence and names the
consumer; a pair whose entry is None passes (the latent cache is served
with `spec_decode` and a vision tower); a model with plain K / V pages
passes every row. The engines' own refusals, through `NativeEngine`, are
in tests/test_ling_state.py, test_moonlight.py, test_mellum.py and
test_trinity.py.
"""
import dataclasses
import types

import pytest

from dynamo_tpu.engine.config import (
    UNSERVED, EngineConfig, ModelConfig, VisionConfig, refuse_unserved,
)

# column of UNSERVED -> (a model that holds that store alone, its opening)
STORES = {
    1: (ModelConfig(name="state", num_layers=4, linear_group_size=2,
                    linear_head_dim=16), "recurrent state"),
    2: (ModelConfig(name="latent", kv_lora_rank=32, qk_rope_head_dim=8),
        "ONE cache leaf"),
    3: (ModelConfig(name="window", num_layers=4, window_pool=True,
                    sliding_window=8, layer_types=(
                        "sliding_attention",) * 3 + ("full_attention",)),
        "3 sliding layers"),
}
PLAIN = ModelConfig(name="plain")
MESH = types.SimpleNamespace(size=2, shape={"tp": 2})
VISION = VisionConfig(image_size=28, patch_size=14, hidden_size=16,
                      intermediate_size=32, num_layers=1, num_heads=2)

# consumer -> (model fields, engine fields, call arguments, what the
# message says of it)
ASKS = {
    "feature": ({}, {}, dict(feature="the shared KV pool"),
                "the shared KV pool"),
    "another store": (dict(kv_lora_rank=32, qk_rope_head_dim=8), {}, {},
                      "beside the window layers"),
    "mesh": ({}, {}, dict(mesh=MESH), "a {'tp': 2} mesh"),
    "kv_quant": ({}, dict(kv_quant="int8"), {}, "kv_quant='int8'"),
    "quant": (dict(quant="int8"), {}, {}, "quant='int8'"),
    "decode_kernel": (dict(decode_kernel="on"), {}, {},
                      "decode_kernel='on'"),
    "vision": (dict(vision=VISION), {}, {}, "a vision tower"),
    "tiers": ({}, dict(host_pages=4), {}, "--host-pages"),
    "spec_decode": ({}, dict(spec_decode="ngram"), {},
                    "spec_decode='ngram'"),
}


def ask(model, consumer):
    model_kw, engine_kw, call_kw, says = ASKS[consumer]
    refuse_unserved(dataclasses.replace(model, **model_kw),
                    EngineConfig(**engine_kw), **call_kw)


def test_every_row_has_a_way_to_ask_for_it():
    assert [row[0] for row in UNSERVED] == list(ASKS)
    assert all(len(row) == 1 + len(STORES) for row in UNSERVED)


@pytest.mark.parametrize("row", UNSERVED, ids=lambda row: row[0])
@pytest.mark.parametrize("column", [0, *STORES],
                         ids=["plain", "state", "latent", "window"])
def test_a_store_refuses_a_consumer_by_name_or_is_served_with_it(
        column, row):
    consumer = row[0]
    if not column:
        ask(PLAIN, consumer)          # plain K / V pages: every row passes
        return
    model, opening = STORES[column]
    if row[column] is None:
        ask(model, consumer)          # served with it
        return
    with pytest.raises(ValueError, match=opening) as err:
        ask(model, consumer)
    # the one consumer asked for, by name, and this store's reason
    said = str(err.value).split("not served with it yet: ")[1]
    assert ASKS[consumer][3] in said and said.endswith(row[column])
    assert "; " not in said


def test_the_first_store_with_a_reason_speaks():
    """A model with a state AND a latent cache (Ling): the state is asked
    first; where it is served (no row of its own is hit) the next store
    speaks."""
    both = dataclasses.replace(STORES[1][0], kv_lora_rank=32,
                               qk_rope_head_dim=8)
    with pytest.raises(ValueError, match="recurrent state.*mesh"):
        refuse_unserved(both, mesh=MESH)
    # a model-only call asks about no engine feature
    refuse_unserved(both)
    refuse_unserved(both, EngineConfig())
