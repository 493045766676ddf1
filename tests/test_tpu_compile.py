"""Compile-only checks of the unified emulator kernel for a TPU v5e.

The TPU compiler is installed with jaxlib, and it compiles for a chip
that is described, not attached: these tests run on the CPU and catch
what interpret mode cannot (Mosaic's block-tiling rule, gathers it does
not lower, scoped-VMEM overflow) at the real widths of the MLP
projections of gemma3-1b and DeepSeek-Coder-33B.  The topology is
described inside a fixture, never at import time: only one process may
load the TPU library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.rram_ps32 import CASE_A
from repro.core import conv4xbar
from repro.kernels.emulator_block.emulator_block import (
    emulator_block_unified_pallas)
from repro.models.common import init_params
from repro.nonideal import N_SCENARIO_FEATURES

V5E_HBM_BYTES = 16 * 1024 ** 3
M_PREFILL = 32                     # batch 2 x prompt 16 rows per matmul


def _mlp_shapes(arch="gemma3-1b"):
    c = get_config(arch)
    return {"gate_up": (c.d_model, c.d_ff), "down": (c.d_ff, c.d_model)}


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    # the compiler would otherwise write its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(one_chip, site, corner="ideal", arch="gemma3-1b", M=M_PREFILL):
    """``site``: an MLP site of ``arch`` by name, or its (K, N).
    ``corner``: ``ideal`` (no shift), or a conditioned net's fc0 shift
    ``sfeat @ f0_scen`` -- ``flat`` for a whole-plan corner (a grid-constant
    operand), ``tiled`` for per-tile features (one row a block, the
    block-indexed operand the stressed corner runs)."""
    K, N = site if isinstance(site, tuple) else _mlp_shapes(arch)[site]
    g = CASE_A
    blk_in = g.tiles * g.rows
    NB, NO = -(-K // blk_in), N // g.outputs
    n_periph = 2 if corner == "ideal" else 2 + N_SCENARIO_FEATURES
    schema = conv4xbar.conv4xbar_schema(g, n_periph=n_periph)
    eparams = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0),
                                                 schema))

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fwd(ep, gn, u, pos, *sfeat):
        aux = conv4xbar.blocklast_weights(ep, g)
        shift = sfeat[0] @ aux["f0_scen"] if sfeat else None
        return emulator_block_unified_pallas(aux, gn, u, pos, shift=shift,
                                             interpret=False)

    args = (jax.tree.map(lambda a: sds(a.shape, a.dtype), eparams),
            sds((NB, NO, g.tiles, g.rows, g.cols)),
            sds((M, NB, g.tiles, g.rows)),
            sds((M, NB, g.tiles, g.rows)))
    if corner == "flat":
        args += (sds((N_SCENARIO_FEATURES,)),)
    elif corner == "tiled":
        args += (sds((NB * NO, N_SCENARIO_FEATURES)),)
    return jax.jit(fwd).lower(*args).compile()


# stage 1's weight, kron(I_Gc, w1k[kk]) for the k1 = 2 window positions
# of one lane chunk of Gc = 16 row groups: 256 (g, c) lanes in, 128 out
STAGE1_WEIGHT = "f32[2,256,128]"
STAGE1_OPERAND = 7                  # after u, pos, g_norm, shift, ev, eg, b0


def _kernel_operands(text):
    """The kernel's operand shapes, from its custom call's layout
    constraints in the compiled HLO text."""
    (line,) = [ln for ln in text.splitlines() if "custom-call(" in ln
               and "emulator_block_unified" in ln]
    cons = line.split("operand_layout_constraints={", 1)[1]
    return re.findall(r"\w+\[[\d,]*\]", cons.split("}}", 1)[0])


def _check(compiled, stage1_weight=None):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if stage1_weight is not None:
        assert _kernel_operands(text)[STAGE1_OPERAND] == stage1_weight
    # the kernel's output reaches the wrapper's relayouts through bitcasts
    # alone, so the one device op a launch that names the kernel is the
    # kernel itself (a consumer's HLO text names its operands)
    for line in text.splitlines():
        if "%emulator_block_unified" in line and "custom-call(" not in line:
            assert " bitcast(" in line, line
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


@pytest.mark.parametrize("site", ["gate_up", "down"])
@pytest.mark.parametrize("corner", ["ideal", "flat", "tiled"])
def test_unified_kernel_compiles_f32(one_chip, site, corner):
    """At the served widths the kernel compiles for the v5e at every
    corner's shift operand, and is a Mosaic custom call."""
    _check(_compile(one_chip, site, corner))


@pytest.mark.parametrize("site", ["gate_up", "down"])
@pytest.mark.parametrize("M", [4, 8])
@pytest.mark.parametrize("corner", ["ideal", "tiled"])
def test_unified_kernel_compiles_dsc33b_f32(one_chip, site, M, corner):
    """At DeepSeek-Coder-33B's MLP widths (7168 -> 19200 -> 7168) and the
    decode and prefill row counts of its benchmark cell, the row tile
    (``STEP_ROWS`` stacked rows a grid step) fits the default scoped
    VMEM, with the ideal corner's shift or a per-tile one, and stage 1
    contracts against one lane chunk's weight."""
    _check(_compile(one_chip, site, corner, arch="deepseek-coder-33b", M=M),
           STAGE1_WEIGHT)


@pytest.mark.parametrize("site", [(4096, 4096), (4096, 1024)],
                         ids=["q_o", "k_v"])
def test_unified_kernel_compiles_phi35moe_attn(one_chip, site):
    """At Phi-3.5-MoE's attention widths and the 32 rows of its prefill
    cell (eight row tiles of 4), the kernel fits, its output reaches
    the wrapper through bitcasts, one kernel op a launch, and stage 1
    contracts against one lane chunk's weight."""
    _check(_compile(one_chip, site, M=32), STAGE1_WEIGHT)
