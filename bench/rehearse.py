#!/usr/bin/env python3
"""Compile-only rehearsal of a configuration for a described TPU v5e (by
hand, on a host with no chip; nothing runs):

    JAX_PLATFORMS=cpu python bench/rehearse.py deepseek-coder-33b.x1 \\
        --rows 4 --rows 8

For the configuration's digital serving steps at full width (prefill of
one row of ``--prompt`` tokens, and one batched decode over ``--slots``
rows) and for the unified emulator kernel at every crossbar site and each
``--rows`` count, it prints the compiler's ``memory_analysis()``.  The
program's own serving step takes its CPU branch here, so the crossbar
sites are compiled as the kernel alone.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def mem(compiled) -> str:
    m = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
    return ", ".join(f"{f.split('_size')[0]} {getattr(m, f, 0)}"
                     for f in fields)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--rows", type=int, action="append", default=None)
    ap.add_argument("--prompt", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import harness as H
    from repro.configs.base import ParallelConfig
    from repro.configs.rram_ps32 import BLOCKS
    from repro.core import conv4xbar
    from repro.kernels.emulator_block.emulator_block import (
        emulator_block_unified_pallas)
    from repro.models import model as M
    from repro.models.common import abstract_params, init_params
    from repro.runtime import steps as S

    conf = H.load_json("configs", args.config + ".json")
    cfg = H.arch_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
    params = jax.tree.map(sds, abstract_params(M.model_schema(cfg),
                                               dtype=jnp.bfloat16))
    pcfg = ParallelConfig(attn_block_kv=min(1024, args.prompt),
                          xent_chunk=128, scan_chunk=min(256, args.prompt))
    pre = jax.jit(S.make_prefill_step(cfg, pcfg)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, args.prompt), jnp.int32,
                                                sharding=one)}).compile()
    print(f"{cfg.name} digital prefill (1, {args.prompt}): {mem(pre)}")
    cs = M.model_cache_schema(cfg, args.slots, 128)
    cache = jax.tree.map(lambda l: jax.ShapeDtypeStruct(l[0], l[1],
                                                        sharding=one),
                         cs, is_leaf=M._cache_is_leaf)
    dec = jax.jit(S.make_decode_step(cfg, pcfg)).lower(
        params, jax.ShapeDtypeStruct((args.slots, 1), jnp.int32,
                                     sharding=one),
        cache, jax.ShapeDtypeStruct((args.slots,), jnp.int32,
                                    sharding=one)).compile()
    print(f"{cfg.name} digital decode ({args.slots} slots): {mem(dec)}")

    xc = conf["crossbar"]
    geom = BLOCKS[xc["geometry"]]
    ep = init_params(jax.random.PRNGKey(0),
                     conv4xbar.conv4xbar_schema(geom, n_periph=2))
    aux = conv4xbar.blocklast_weights(ep, geom)
    d, f = cfg.d_model, cfg.d_ff
    qf, kvf = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
    shapes = {"mlp": [(d, f), (f, d)],
              "attn": [(d, qf), (d, kvf), (qf, d)]}
    for layer in xc["layers"]:
        for K, N in shapes[layer]:
            NB = -(-K // (geom.rows * geom.tiles))
            g = jax.ShapeDtypeStruct((NB, N, geom.tiles, geom.rows,
                                      geom.cols), jnp.float32, sharding=one)
            for m in args.rows or [4]:
                u = jax.ShapeDtypeStruct((m, NB, geom.tiles, geom.rows),
                                         jnp.float32, sharding=one)
                c = jax.jit(lambda gg, uu, pp: emulator_block_unified_pallas(
                    aux, gg, uu, pp)).lower(g, u, u).compile()
                n = c.as_text().count("tpu_custom_call")
                print(f"{layer} site ({K}, {N}) at {m} rows: kernel x{n}, "
                      f"{mem(c)}")


if __name__ == "__main__":
    main()
