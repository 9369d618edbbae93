"""AOT-compile the llama3-70b scale-out plan on a virtual pp4 x tp4 mesh
and report per-device compiled memory (spawned by test_70b_memory.py with
xla_force_host_platform_device_count=16; prints one JSON line).

No arrays are ever materialized: params/cache enter as ShapeDtypeStructs
via jax.eval_shape and the decode window + a prefill chunk are lowered and
compiled AOT. XLA's CompiledMemoryStats is per-device under SPMD, so the
numbers are the HBM a real v5e chip would need for this plan.
"""
import functools
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu.engine.config import get_model_config  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.llama import AttnMetadata  # noqa: E402
from dynamo_tpu.models.pp import pp_decode_window, pp_forward  # noqa: E402
from dynamo_tpu.parallel.mesh import make_mesh  # noqa: E402


def per_device_mem(compiled) -> dict:
    ma = compiled.memory_analysis()
    # resident: what must LIVE on the device across steps — sharded params
    # + cache + step I/O, net of donation aliasing (cache updated in
    # place). This is the cross-platform invariant: a sharding regression
    # (e.g. layers silently replicated) multiplies it 4-16x. temp is
    # reported for information only: the CPU backend materializes layout
    # copies of the scanned weight stacks that the TPU compiler fuses, so
    # CPU temp wildly overstates TPU workspace.
    return {
        "resident": (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes),
        "temp_cpu": ma.temp_size_in_bytes,
    }


def main():
    import dataclasses

    pp, tp = 4, 4
    cfg = get_model_config("llama3-70b")  # bf16, 80 layers
    if "--int8" in sys.argv:
        # weight-only int8 (ops/quant.py): the dense projections become
        # int8 + scales, roughly halving resident weight bytes — the
        # 70B-on-fewer-chips story. pp2 x tp4 = 8 devices.
        cfg = dataclasses.replace(cfg, quant="int8")
        pp = 2
    mesh = make_mesh(pp=pp, tp=tp, devices=jax.devices()[:pp * tp])

    # serving shapes: 8 slots x 2048-token contexts, page 64
    slots, page_size, ctx = 8, 64, 2048
    num_pages = slots * ctx // page_size
    pages_per_seq = ctx // page_size
    n_steps = 8  # scan length; pp window memory is step-count-invariant

    from dynamo_tpu.ops.quant import quantize_params

    def make_params(k):
        p = llama.init_params(k, cfg)
        return quantize_params(p, cfg) if cfg.quant == "int8" else p

    params = jax.eval_shape(make_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, num_pages,
                                                    page_size))
    param_bytes = sum(np.prod(x.shape) * x.dtype.itemsize
                      for x in jax.tree.leaves(params))

    sds = jax.ShapeDtypeStruct
    dec = jax.jit(
        functools.partial(pp_decode_window, cfg, (128001,), mesh, n_steps,
                          page_size, True),
        donate_argnums=(1,)).lower(
        params, cache,
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots, pages_per_seq), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), bool), sds((slots, 2), jnp.int32),
        sds((slots,), jnp.float32), sds((slots,), jnp.int32),
        sds((slots,), jnp.float32), sds((slots,), jnp.int32)).compile()
    dec_mem = per_device_mem(dec)

    # batched prefill chunk (the other big live set): 8 x 128 tokens
    chunk = 128
    pf = jax.jit(
        lambda p, c, t, pos, pt, kl, wi: pp_forward(
            p, cfg, t, c,
            AttnMetadata(positions=pos, page_table=pt, kv_lens=kl,
                         write_idx=wi), mesh)[1],
        donate_argnums=(1,)).lower(
        params, cache, sds((slots, chunk), jnp.int32),
        sds((slots, chunk), jnp.int32),
        sds((slots, pages_per_seq), jnp.int32),
        sds((slots,), jnp.int32),
        sds((slots, chunk), jnp.int32)).compile()
    pf_mem = per_device_mem(pf)

    print(json.dumps({
        "mesh": f"pp{pp}xtp{tp}",
        "param_bytes_total": int(param_bytes),
        "decode": dec_mem,
        "prefill": pf_mem,
    }))


if __name__ == "__main__":
    main()
