"""Public entry point: the emulator serving math, dispatched by platform."""
from __future__ import annotations

import jax

from repro.core.conv4xbar import apply_blocklast, blocklast_precompute
from repro.kernels.emulator_block.emulator_block import (
    emulator_block_unified_pallas)

# batch rows a lax.map chunk of the XLA schedule: small chunks keep the
# per-chunk (rows x blocks) working set small on the CPU
CHUNK = 2


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def runs_kernel(use_pallas: bool | None) -> bool:
    """Whether ``emulator_block_unified`` takes the Pallas kernel: always
    on a TPU, elsewhere only when asked (in interpret mode)."""
    return _on_tpu() or bool(use_pallas)


def emulator_block_unified(aux: dict, g_norm: jax.Array, u01: jax.Array,
                           pos01: jax.Array, *,
                           shift: jax.Array | None = None,
                           pre: dict | None = None,
                           use_pallas: bool | None = None) -> jax.Array:
    """Single entry point for the emulator serving math, every corner.

    Dispatches ONE dual-rail evaluation -- ``shift`` is the precomputed
    scenario epilogue (``sfeat @ aux["f0_scen"]``, None at the ideal
    corner) -- to the fused Pallas kernel
    (``emulator_block_unified_pallas``, at its ``BLOCK_N`` blocks a step)
    or the chunked XLA evaluation (``conv4xbar.apply_blocklast``,
    ``CHUNK`` rows a chunk, over ``pre``, the ``blocklast_precompute`` of
    ``g_norm``, derived here when None).

    On a TPU the kernel always runs compiled: ``use_pallas`` is ignored
    there, and nothing falls back to the XLA schedule or the
    interpreter.  Elsewhere ``use_pallas=True`` runs the kernel in
    interpret mode (tests) and the default is the XLA schedule.  The two
    agree to f32 rounding (``emulator_block_unified_pallas``).
    Returns (2, M*NB*NO, O).
    """
    if runs_kernel(use_pallas):
        return emulator_block_unified_pallas(
            aux, g_norm, u01, pos01, shift=shift, interpret=not _on_tpu())
    if pre is None:
        pre = blocklast_precompute(aux, g_norm)
    return apply_blocklast(aux, pre, u01, pos01, chunk=CHUNK,
                           fc0_shift=shift)
