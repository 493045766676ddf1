"""Public wrapper: builds the stage plan from the block geometry."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.rram_ps32 import BlockGeometry
from repro.core.conv4xbar import (apply_blocklast, blocklast_precompute,
                                  build_stages)
from repro.kernels import autotune
from repro.kernels.emulator_block.emulator_block import (
    emulator_block_grid_pallas, emulator_block_pallas,
    emulator_block_unified_pallas)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def emulator_block(params: dict, x: jax.Array, periph: jax.Array,
                   geom: BlockGeometry, *, block_n: int = 256):
    """Fused Conv4Xbar forward. x: (N, C, D, H, W) normalized; -> (N, O)."""
    stages = build_stages(geom)
    return emulator_block_pallas(params, x, periph, stages,
                                 block_n=block_n, interpret=not _on_tpu())


def emulator_block_grid(params: dict, v01: jax.Array, g_norm: jax.Array,
                        geom: BlockGeometry, *, block_m: int = 128,
                        interpret: bool = None):
    """Batched serving variant: 2-D grid (batch tiles, NB*NO block index).

    v01: (M, NB, D, H) normalized voltages; g_norm: (NB*NO, D, H, W) shared
    normalized conductance features; -> (M, NB*NO, O)."""
    stages = build_stages(geom)
    if interpret is None:
        interpret = not _on_tpu()
    return emulator_block_grid_pallas(params, v01, g_norm, stages,
                                      block_m=block_m, interpret=interpret)


def _dummy_like(tree):
    """Concrete stand-ins with the tree's shapes/dtypes.  Non-array
    leaves (the static kernel widths in aux) pass through."""
    return jax.tree_util.tree_map(
        lambda a: jnp.full(a.shape, 0.1, a.dtype)
        if hasattr(a, "shape") else a, tree)


def _traced(tree) -> bool:
    return any(isinstance(a, jax.core.Tracer)
               for a in jax.tree_util.tree_leaves(tree))


def runs_kernel(use_pallas: bool | None) -> bool:
    """Whether ``emulator_block_unified`` takes the Pallas kernel: always
    on a TPU, elsewhere only when asked (in interpret mode)."""
    return _on_tpu() or bool(use_pallas)


# blocks per grid step the tuner may pick; each compiles for the v5e at
# the gate/up and down widths of the served models within the default
# scoped VMEM (tests/test_tpu_compile.py)
BLOCK_N_CANDIDATES = (128, 256)


def emulator_block_unified(aux: dict, g_norm: jax.Array, u01: jax.Array,
                           pos01: jax.Array, *,
                           shift: jax.Array | None = None,
                           pre: dict | None = None,
                           use_pallas: bool | None = None,
                           chunk: int | None = None,
                           block_n: int | None = None,
                           tune: bool = True,
                           compute_dtype=jnp.float32) -> jax.Array:
    """Single entry point for the emulator serving math, every corner.

    Dispatches ONE dual-rail evaluation -- ``shift`` is the precomputed
    scenario epilogue (``sfeat @ aux["f0_scen"]``, None at the ideal
    corner) -- to the fused Pallas kernel
    (``emulator_block_unified_pallas``) or the chunked XLA evaluation
    (``conv4xbar.apply_blocklast``, over ``pre``, the
    ``blocklast_precompute`` of ``g_norm``, derived here when None).

    On a TPU the kernel always runs compiled: ``use_pallas`` is ignored
    there, and nothing falls back to the XLA schedule or the
    interpreter.  Elsewhere ``use_pallas=True`` runs the kernel in
    interpret mode (tests) and the default is the XLA schedule.  The two
    agree to f32 rounding (``emulator_block_unified_pallas``).

    ``block_n``/``chunk`` left as None are resolved by the autotuner
    (``kernels.autotune``): a cache hit, else -- only when every operand
    is concrete -- a sweep of real compiles, else the heuristic default
    (128 / 2).  Under an enclosing trace (the serving steps) the sweep
    never fires: it would time tracing, not the kernel.  ``tune=False``
    takes the default directly (the executor's ``shard_map`` bodies run
    per-shard lattice slices the tuner never measured).
    Returns (2, M*NB*NO, O).
    """
    M = u01.shape[0]
    NB, NO, D, H, W = g_norm.shape
    n_out = aux["fcs"][-1][0].shape[1]
    ops = (aux, g_norm, u01, pos01, shift)
    on_tpu = _on_tpu()

    if runs_kernel(use_pallas):
        if block_n is None and tune:
            key_parts = (M, NB, NO, D, H, W, n_out,
                         jnp.dtype(compute_dtype).name, not on_tpu)
            state = {}

            def measure(cfg):
                bn = cfg["block_n"]
                if "dummies" not in state:
                    state["dummies"] = _dummy_like(ops)
                da, dg, du, dpos, dsh = state["dummies"]
                if bn not in state:
                    state[bn] = jax.jit(
                        lambda gg, uu, qq, ss, bn=bn:
                        emulator_block_unified_pallas(
                            da, gg, uu, qq, shift=ss, block_n=bn,
                            interpret=not on_tpu,
                            compute_dtype=compute_dtype))
                jax.block_until_ready(state[bn](dg, du, dpos, dsh))

            cfg = autotune.best_config(
                "emulator_unified", key_parts,
                [{"block_n": b} for b in BLOCK_N_CANDIDATES],
                None if _traced(ops) else measure, {"block_n": 128})
            block_n = cfg["block_n"]
        return emulator_block_unified_pallas(
            aux, g_norm, u01, pos01, shift=shift,
            block_n=128 if block_n is None else block_n,
            interpret=not on_tpu, compute_dtype=compute_dtype)

    if pre is None:
        pre = blocklast_precompute(aux, g_norm)
    if chunk is None and tune:
        key_parts = (M, NB, NO, D, H, W, n_out)
        state = {}             # lazy dummies + per-config compiled fns

        def measure(cfg):
            ch = cfg["chunk"]
            if "dummies" not in state:
                state["dummies"] = _dummy_like((aux, pre, u01, pos01,
                                                shift))
            da, dp, du, dpos, dsh = state["dummies"]
            if ch not in state:
                state[ch] = jax.jit(
                    lambda uu, qq, ss, ch=ch: apply_blocklast(
                        da, dp, uu, qq, chunk=ch, fc0_shift=ss))
            jax.block_until_ready(state[ch](du, dpos, dsh))

        cfg = autotune.best_config(
            "blocklast_chunk", key_parts,
            [{"chunk": c} for c in (1, 2, 4, 8)],
            None if _traced(ops + (pre,)) else measure, {"chunk": 2})
        chunk = cfg["chunk"]
    return apply_blocklast(aux, pre, u01, pos01,
                           chunk=2 if chunk is None else chunk,
                           fc0_shift=shift)
