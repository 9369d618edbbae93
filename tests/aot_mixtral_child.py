"""AOT-compile the mixtral-8x7b serving plan on a virtual ep4 x tp2 mesh
and report per-device compiled memory (spawned by test_70b_memory.py;
prints one JSON line; --int8 switches on weight-only quantization of the
attention + stacked expert tensors, ops/quant.py).

Same method as aot_70b_child.py: ShapeDtypeStruct params via
jax.eval_shape, AOT lower+compile, per-device CompiledMemoryStats; the
RESIDENT set (sharded params + paged KV + step I/O net of donation) is
the cross-platform number.
"""
import dataclasses
import functools
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from dynamo_tpu.engine.config import get_model_config  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.llama import AttnMetadata  # noqa: E402
from dynamo_tpu.ops.quant import quantize_params, quantize_shardings  # noqa: E402
from dynamo_tpu.parallel.mesh import make_mesh  # noqa: E402


def main():
    ep, tp = 4, 2
    cfg = get_model_config("mixtral-8x7b")
    if "--int8" in sys.argv:
        cfg = dataclasses.replace(cfg, quant="int8")
    mesh = make_mesh(ep=ep, tp=tp, devices=jax.devices()[:ep * tp])

    slots, page_size, ctx = 8, 64, 2048
    num_pages = slots * ctx // page_size
    pages_per_seq = ctx // page_size
    chunk = 128

    def make_params(k):
        p = llama.init_params(k, cfg)
        return quantize_params(p, cfg) if cfg.quant == "int8" else p

    params = jax.eval_shape(make_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, num_pages,
                                                    page_size))
    param_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(params))

    specs = llama.param_shardings(cfg)
    if cfg.quant == "int8":
        specs = quantize_shardings(specs, cfg)
    p_shd = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
    c_shd = NamedSharding(mesh, llama.cache_sharding(cfg))
    rep = NamedSharding(mesh, P())

    sds = jax.ShapeDtypeStruct

    def fwd(p, c, tokens, pos, pt, kl, wi):
        meta = AttnMetadata(positions=pos, page_table=pt, kv_lens=kl,
                            write_idx=wi)
        _, new_cache, _ = llama.forward(p, cfg, tokens, c, meta, mesh=mesh,
                                        with_aux=True)
        return new_cache

    compiled = jax.jit(
        fwd,
        in_shardings=(p_shd, {"k": c_shd, "v": c_shd},
                      rep, rep, rep, rep, rep),
        donate_argnums=(1,)).lower(
        params, cache,
        sds((slots, chunk), jnp.int32), sds((slots, chunk), jnp.int32),
        sds((slots, pages_per_seq), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, chunk), jnp.int32)).compile()
    ma = compiled.memory_analysis()
    print(json.dumps({
        "mesh": f"ep{ep}xtp{tp}",
        "quant": cfg.quant or "bf16",
        "param_bytes_total": int(param_bytes),
        "prefill": {
            "resident": int(ma.argument_size_in_bytes
                            + ma.output_size_in_bytes
                            - ma.alias_size_in_bytes),
            "temp_cpu": int(ma.temp_size_in_bytes),
        },
    }))


if __name__ == "__main__":
    main()
