"""Conv4Xbar: the paper's emulator architecture (Fig. 3, Table 2).

A 3D-CNN whose kernels have depth 1 (tiles axis) and grow along the row axis
(H: 1 -> 2 -> 4 -> 8 with matching strides), mirroring column-wise current
accumulation; then a (1,1,2) conv across the differential column pairs; then
an FCNN 'circuit equation solver' head (128/256 -> 32 -> 16 -> O), CELU
everywhere. Peripheral-circuit features are concatenated before the head.

Two apply paths:
  apply()       -- paper-faithful lax.conv_general_dilated stack
  apply_fused() -- TPU-native algebraic rewrite: each depth-1 strided conv is
                   a blocked matmul over reshaped row groups (MXU-friendly;
                   validated equal to apply() in tests). See DESIGN.md §3.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.rram_ps32 import BlockGeometry
from repro.models.common import ParamSchema


@dataclass(frozen=True)
class ConvStage:
    c_in: int
    c_out: int
    kernel: Tuple[int, int, int]     # (D, H, W)
    stride: Tuple[int, int, int]


def build_stages(geom: BlockGeometry) -> List[ConvStage]:
    """Table 2 stack, generalized to any (C, D, H, W) geometry."""
    stages = [ConvStage(geom.features, 16, (1, 1, 1), (1, 1, 1))]
    h = geom.rows
    plan = [(16, 8, 2), (8, 4, 4), (4, 32, 8)]
    for c_in, c_out, k in plan:
        k = min(k, h)
        stages.append(ConvStage(c_in, c_out, (1, k, 1), (1, k, 1)))
        h = h // k
    # across differential column pairs; stride 2 when W > 2 (case B: the
    # paper's Linear(256, 32) implies stride (1,1,2) -- Table 2 typo)
    w_stride = 1 if geom.cols <= 2 else 2
    stages.append(ConvStage(32, 32, (1, 1, 2), (1, 1, w_stride)))
    return stages


def _out_size(size, k, s):
    return (size - k) // s + 1


def conv_out_sizes(stages: Sequence[ConvStage], d: int, h: int, w: int):
    """Spatial output dims of the stage stack for a (d, h, w) input."""
    for st in stages:
        d = _out_size(d, st.kernel[0], st.stride[0])
        h = _out_size(h, st.kernel[1], st.stride[1])
        w = _out_size(w, st.kernel[2], st.stride[2])
    return d, h, w


def flat_features(geom: BlockGeometry) -> int:
    d, h, w = conv_out_sizes(build_stages(geom), geom.tiles, geom.rows,
                             geom.cols)
    return 32 * d * h * w


def conv4xbar_schema(geom: BlockGeometry, n_periph: int = 0,
                     head: Sequence[int] = (32, 16)):
    """Parameter schema (shapes + shardings + init) for one emulator."""
    s = {}
    for i, st in enumerate(build_stages(geom)):
        fan_in = st.c_in * int(np.prod(st.kernel))
        s[f"conv{i}_w"] = ParamSchema(
            (st.c_out, st.c_in) + st.kernel, P(None), "normal",
            math.sqrt(2.0 / fan_in))
        s[f"conv{i}_b"] = ParamSchema((st.c_out,), P(None), "zeros")
    d_in = flat_features(geom) + n_periph
    dims = [d_in, *head, geom.outputs]
    for i in range(len(dims) - 1):
        s[f"fc{i}_w"] = ParamSchema((dims[i], dims[i + 1]), P(None), "normal",
                                    math.sqrt(2.0 / dims[i]))
        s[f"fc{i}_b"] = ParamSchema((dims[i + 1],), P(None), "zeros")
    s["_meta"] = ParamSchema((3,), P(None), "zeros")   # (n_stages, n_fc, n_periph)
    return s


def n_periph_of(params, geom: BlockGeometry) -> int:
    """Peripheral-feature width a trained param set was bound to (the fc0
    rows past the conv flatten).  Static even for traced params -- shapes
    are aval data -- so callers may branch on it at trace time.  ``> 2``
    means the net is scenario-conditioned: rows ``2:`` of the peripheral
    block consume ``nonideal.scenario_features`` (docs/emulator.md)."""
    return int(params["fc0_w"].shape[0]) - flat_features(geom)


def _head(params, h, n_fc):
    for i in range(n_fc):
        h = h @ params[f"fc{i}_w"] + params[f"fc{i}_b"]
        if i < n_fc - 1:
            h = jax.nn.celu(h)
    return h


def _n_stages(params):
    return len([k for k in params if k.startswith("conv") and k.endswith("_w")])


def _n_fc(params):
    return len([k for k in params if k.startswith("fc") and k.endswith("_w")])


def apply(params, x: jax.Array, periph: jax.Array | None = None) -> jax.Array:
    """Paper-faithful path. x: (B, C, D, H, W) -> (B, O)."""
    h = x
    for i in range(_n_stages(params)):
        w = params[f"conv{i}_w"]
        stride = _stride_of(w, h)
        h = jax.lax.conv_general_dilated(
            h, w, window_strides=stride, padding="VALID",
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
        h = jax.nn.celu(h + params[f"conv{i}_b"][None, :, None, None, None])
    h = h.reshape(h.shape[0], -1)
    if periph is not None:
        h = jnp.concatenate([h, periph.astype(h.dtype)], axis=-1)
    return _head(params, h, _n_fc(params))


def _stride_of(w, h):
    """Recover the stage stride from kernel shape (stride == kernel except
    the final (1,1,2) stage where stride_w is 2 iff W_in > 2)."""
    kd, kh, kw = w.shape[2], w.shape[3], w.shape[4]
    if (kd, kh, kw) == (1, 1, 2):
        return (1, 1, 1 if h.shape[4] <= 2 else 2)
    return (kd, kh, kw)


# --------------------------------------------------------------------------- #
# Blockified serving fast path (channels-last, conductance precomputed)
#
# At system level (core/analog.py) the emulator evaluates B * NB * NO blocks
# per matmul for BOTH voltage rails, but the conductance features are
# batch-constant: only the voltage channel changes per call.  The fast path
#   * precomputes stage-0's conductance contribution once per weight plan
#     (g0 = w0_g * g_norm + b0), together with the zero-voltage block
#     response celu(g0) and its stage-1 projection y0 = celu(g0) @ W1 + b1;
#   * exploits dual-rail complementarity: at every wordline exactly one of
#     (v+ = relu(x), v- = relu(-x)) is nonzero, so the expensive stage-0
#     CELU is evaluated ONCE on |x| (half the rail-stacked batch) and both
#     rails are reconstructed from delta = celu(v0 + g0) - celu(g0) --
#     delta rows with v = 0 vanish exactly;
#   * moves the rail mask to the stage-1 GEMM *output* by splitting the
#     row-window contraction (the mask is constant across the channel dim),
#     so no masked 8M-element copies are materialized;
#   * keeps activations channels-LAST (n, D, W, H, C) so every conv stage is
#     a reshape + trailing-dim matmul -- no layout transposes on the hot
#     path -- and evaluates in cache-sized batch chunks (lax.map);
#   * folds the constant peripheral features (gain=1, offset=0) into the
#     first FC bias, skipping the per-sample concat.
# Numerically equivalent to apply()/apply_fused() within fp32 tolerance
# (same contractions, different association order); see tests/test_analog_fastpath.
# --------------------------------------------------------------------------- #
def blocklast_weights(params, geom: BlockGeometry,
                      periph_const=(1.0, 0.0)) -> dict:
    """Repack emulator params for the channels-last blockified fast path."""
    assert geom.features == 2, "expects (V, G) cell features"
    stages = build_stages(geom)
    aux = {}
    w0 = params["conv0_w"][:, :, 0, 0, 0]             # (C0, 2)
    aux["w0v"], aux["w0g"] = w0[:, 0], w0[:, 1]
    aux["b0"] = params["conv0_b"]
    hstages = []
    for i, st in enumerate(stages[1:-1], start=1):
        k = st.kernel[1]
        w = params[f"conv{i}_w"][:, :, 0, :, 0]       # (O, I, k)
        wk = w.transpose(2, 1, 0).reshape(k * st.c_in, st.c_out)
        hstages.append((wk, params[f"conv{i}_b"], k))
    aux["hstages"] = tuple(hstages)
    # stage 1 split by row-window position kk: (k1, C0, O1) so the dual-rail
    # mask (constant across channels) can be applied to each position's
    # GEMM output -- one (C0, O1) contraction per kk instead of a k1^2
    # cross-position GEMM whose off-diagonal blocks were discarded
    w1, _, k1 = hstages[0]
    c0 = stages[0].c_out
    o1 = w1.shape[1]
    aux["w1k"] = w1.reshape(k1, c0, o1)
    iw = len(stages) - 1
    st = stages[iw]
    kw = st.kernel[2]
    w = params[f"conv{iw}_w"][:, :, 0, 0, :]          # (O, I, kw)
    aux["wstage"] = (w.transpose(2, 1, 0).reshape(kw * st.c_in, st.c_out),
                     params[f"conv{iw}_b"], kw)
    # fc0: permute rows from (c, d, h, w) flatten order to (d, h, w, c), and
    # fold the constant peripheral drive into the bias.
    d, h, wd = conv_out_sizes(stages, geom.tiles, geom.rows, geom.cols)
    cf = stages[-1].c_out
    flat = cf * d * h * wd
    f0 = params["fc0_w"]
    perm = f0[:flat].reshape(cf, d, h, wd, -1).transpose(1, 2, 3, 0, 4)
    perm = perm.reshape(flat, -1)
    n_periph = f0.shape[0] - flat
    b0 = params["fc0_b"]
    if n_periph:
        # pad with zeros past the supplied constants: a conditioned net's
        # scenario-feature rows (2:) encode the IDEAL corner as exactly 0,
        # so the zero fold keeps the plain fast path bit-identical to the
        # unconditioned one; the scenario forward adds the corner's
        # contribution as a traced fc0 shift (apply_blocklast(fc0_shift=))
        pc = jnp.zeros((n_periph,), f0.dtype)
        pc = pc.at[:min(len(periph_const), n_periph)].set(
            jnp.asarray(periph_const[:n_periph], f0.dtype))
        b0 = b0 + pc @ f0[flat:]
    if n_periph > len(periph_const):
        # scenario-feature rows of fc0: the conditioned corner's fc0
        # contribution is sfeat @ f0_scen, a per-call bias shift
        aux["f0_scen"] = f0[flat + len(periph_const):]
    fcs = [(perm, b0)]
    for i in range(1, _n_fc(params)):
        fcs.append((params[f"fc{i}_w"], params[f"fc{i}_b"]))
    aux["fcs"] = tuple(fcs)
    return aux


def stage0_conductance(aux: dict, g_norm: jax.Array) -> jax.Array:
    """g_norm: (NB, NO, D, H, W) normalized conductance features ->
    (NB, NO, D, W, H, C0) precomputed stage-0 pre-activation contribution."""
    g = g_norm.transpose(0, 1, 2, 4, 3)               # (NB, NO, D, W, H)
    return g[..., None] * aux["w0g"] + aux["b0"]


def blocklast_precompute(aux: dict, g_norm: jax.Array) -> dict:
    """Batch-independent per-plan tensors for apply_blocklast.

    g0k:    stage-0 pre-activation conductance contribution, split by
            row-window position: (k1, NB, NO, D, W, G, C0) so the hot
            loop's per-kk slices are contiguous views
    celu0k: the zero-voltage stage-0 response celu(g0), same split
    y0:     its stage-1 projection celu(g0) @ W1 + b1 (pre-activation),
            (NB*NO*D*W*G, O1)
    """
    g0 = stage0_conductance(aux, g_norm)              # (NB, NO, D, W, H, C0)
    celu0 = jax.nn.celu(g0)
    w1, b1, k1 = aux["hstages"][0]
    y0 = celu0.reshape(-1, w1.shape[0]) @ w1 + b1     # (NB*NO*D*W*G, O1)
    nb, no, d, w, h, c0 = g0.shape
    shp = (nb, no, d, w, h // k1, k1, c0)             # H -> (G, kk)
    g0k = jnp.moveaxis(g0.reshape(shp), 5, 0)
    celu0k = jnp.moveaxis(celu0.reshape(shp), 5, 0)
    return {"g0k": g0k, "celu0k": celu0k, "y0": y0}


def _tail_stages(aux: dict, h: jax.Array, n: int, shp,
                 fc0_shift: jax.Array | None = None) -> jax.Array:
    """Conv stages 2.. + FC head on channels-last rows.  h: 2-D (rows, C)
    laid out as shp=(n, D, W, G) x channels; -> (n, O).  ``fc0_shift`` is
    an optional per-call bias shift on fc0's pre-activation (the
    conditioned emulator's scenario-feature contribution): either a flat
    ``(fc0_out,)`` vector (whole-plan corner) or a per-tile ``(nblk,
    fc0_out)`` lattice -- rows are laid out block-innermost (NB*NO cycles
    fastest), so a 2-D shift folds onto ``(n // nblk, nblk, fc0_out)``
    and each block gets its own scenario contribution."""
    for wk, b, k in aux["hstages"][1:]:
        # one flat GEMM over (k*C) -- batched matmuls over small trailing
        # matrices are pathologically slow on CPU backends
        h = jax.nn.celu(jnp.matmul(h.reshape(-1, wk.shape[0]), wk) + b)
        shp = shp[:3] + (shp[3] // k,)
    wk, b, kw = aux["wstage"]
    h = h.reshape(shp + (-1,)).transpose(0, 1, 3, 2, 4)   # (n, D, H, W, C)
    h = jax.nn.celu(jnp.matmul(h.reshape(-1, wk.shape[0]), wk) + b)
    h = h.reshape(n, -1)                              # (d, h, w, c) flatten
    fcs = aux["fcs"]
    for i, (fw, fb) in enumerate(fcs):
        h = jnp.matmul(h, fw) + fb
        if i == 0 and fc0_shift is not None:
            if fc0_shift.ndim == 2:
                nblk, f = fc0_shift.shape
                h = (h.reshape(-1, nblk, f) + fc0_shift).reshape(n, f)
            else:
                h = h + fc0_shift
        if i < len(fcs) - 1:
            h = jax.nn.celu(h)
    return h


def dual_rail_stage1(g0k, celu0k, y0, w0v, w1k, u, pos):
    """Stage 0+1 of the single-pass dual-rail factorization.

    u, pos: (..., G, k1) magnitude drive / positive-rail mask, with the
    leading axes shaped to broadcast against ``g0k[kk]``/``celu0k[kk]``
    (callers insert singleton NO/W axes).  y0: (R, O1) zero-voltage
    stage-1 projection, tiled over the batch rows.  Returns the two
    rails' stage-1 pre-activations ``(y0 + t_pos, y0 + t_full - t_pos)``
    stacked: (2, batch, R, O1).

    Per window position kk, delta_kk = celu(v0 + g0) - celu(g0) is
    contracted over channels only (one (C0, O1) GEMM) and the rail mask
    lands on the GEMM *output* -- half the FLOPs of a cross-position
    (C0, k1*O1) contraction, and no diagonal gather.  The unified Pallas
    kernel evaluates the same factorization in its own 2-d layout
    (``kernels.emulator_block``), equal to f32 rounding."""
    k1, C0, O1 = w1k.shape
    R = y0.shape[0]
    t_full = t_pos = None
    for kk in range(k1):
        v0 = u[..., kk, None] * w0v                   # broadcasts vs g0k[kk]
        delta = jax.nn.celu(v0 + g0k[kk]) - celu0k[kk]
        t = jnp.matmul(delta.reshape(-1, C0), w1k[kk])
        t = t.reshape(-1, R, O1)                      # (batch, R, O1)
        m = jnp.broadcast_to(pos[..., kk, None], delta.shape[:-1] + (1,))
        m = m.reshape(-1, R, 1)
        t_full = t if t_full is None else t_full + t
        tp = t * m
        t_pos = tp if t_pos is None else t_pos + tp
    return jnp.stack([y0[None] + t_pos, y0[None] + t_full - t_pos])


def apply_blocklast(aux: dict, pre: dict, u01: jax.Array, pos01: jax.Array,
                    *, chunk: int = 4,
                    fc0_shift: jax.Array | None = None) -> jax.Array:
    """Single-pass dual-rail blockified forward.

    u01:   (M, NB, D, H) |x|-magnitude wordline drive in [0, 1]
    pos01: (M, NB, D, H) 1.0 where the positive rail is driven (x > 0)
    fc0_shift: optional pre-activation shift -- a conditioned emulator's
    scenario-feature contribution ``sfeat @ aux["f0_scen"]``: either
    ``(fc0_out,)`` (whole-plan corner) or ``(NB*NO, fc0_out)`` (per-tile
    feature operands, one shift per block in lattice order), traced so
    corner/age changes reuse the executable (exactly zero at the ideal
    corner, where the plain path omits it entirely).
    Returns (2, M*NB*NO, O): block outputs of the (v+, v-) rails.

    The stage-0 CELU runs once on the magnitude drive; each rail's stage-1
    pre-activation is reconstructed as y0 + mask-selected delta terms, which
    is exact because delta rows with v = 0 vanish identically."""
    M, NB, D, H = u01.shape
    g0k, celu0k, y0 = pre["g0k"], pre["celu0k"], pre["y0"]
    k1 = g0k.shape[0]
    NO, W, G = g0k.shape[2], g0k.shape[4], g0k.shape[5]

    mc = min(chunk, M)
    padM = (-M) % mc
    if padM:
        u01 = jnp.pad(u01, ((0, padM),) + ((0, 0),) * 3)
        pos01 = jnp.pad(pos01, ((0, padM),) + ((0, 0),) * 3)
    Mp = M + padM
    # wordline index split into (row group G, window position k1), with
    # singleton NO/W axes so the per-kk drive broadcasts against g0k
    ug = u01.reshape(Mp, NB, 1, D, 1, G, k1)
    pg = pos01.reshape(Mp, NB, 1, D, 1, G, k1)

    def one(args):
        uc, mk = args                                 # (mc,NB,1,D,1,G,k1) x2
        h = jax.nn.celu(dual_rail_stage1(g0k, celu0k, y0, aux["w0v"],
                                         aux["w1k"], uc, mk))
        n2 = 2 * mc * NB * NO                         # h: (2, mc, R, O1)
        h = _tail_stages(aux, h.reshape(n2, -1), n2, (n2, D, W, G),
                         fc0_shift=fc0_shift)
        return h.reshape(2, mc * NB * NO, -1)

    ub = ug.reshape((Mp // mc, mc) + ug.shape[1:])
    mb = pg.reshape((Mp // mc, mc) + pg.shape[1:])
    out = jax.lax.map(one, (ub, mb))                  # (nc, 2, mc*NBLK, O)
    out = out.transpose(1, 0, 2, 3).reshape(2, Mp * NB * NO, -1)
    return out[:, :M * NB * NO]


def apply_fused(params, x: jax.Array, periph: jax.Array | None = None) -> jax.Array:
    """TPU-native path: every depth-1 conv rewritten as a reshape + matmul.

    Stage with kernel (1,k,1)/stride (1,k,1):  (B,C,D,H,W) -> group H into
    (H/k, k) and contract (C,k) -> C'.  Final (1,1,2) stage groups W.
    Bit-exact vs apply() (same weights, same arithmetic order up to matmul
    association)."""
    h = x
    for i in range(_n_stages(params)):
        w = params[f"conv{i}_w"]                      # (O, I, kd, kh, kw)
        O, I, kd, kh, kw = w.shape
        B, C, D, H, W = h.shape
        if (kh, kw) == (1, 1):
            # pointwise: (B,C,DHW) x (C,O)
            hm = h.reshape(B, C, D * H * W)
            y = jnp.einsum("bcn,co->bon", hm, w[:, :, 0, 0, 0].T)
            h = y.reshape(B, O, D, H, W)
        elif kw == 1:
            hg = h.reshape(B, C, D, H // kh, kh, W)
            wk = w[:, :, 0, :, 0]                     # (O, I, kh)
            h = jnp.einsum("bcdgkw,ock->bodgw", hg, wk)
            h = h.reshape(B, O, D, H // kh, W)
        else:
            stride_w = _stride_of(w, h)[2]
            wk = w[:, :, 0, 0, :]                     # (O, I, kw)
            if stride_w == kw:
                hg = h.reshape(B, C, D, H, W // kw, kw)
                h = jnp.einsum("bcdhgk,ock->bodhg", hg, wk)
            else:                                      # stride 1, kernel 2
                h = (jnp.einsum("bcdhw,oc->bodhw", h[..., :-1], wk[:, :, 0])
                     + jnp.einsum("bcdhw,oc->bodhw", h[..., 1:], wk[:, :, 1]))
        h = jax.nn.celu(h + params[f"conv{i}_b"][None, :, None, None, None])
    h = h.reshape(h.shape[0], -1)
    if periph is not None:
        h = jnp.concatenate([h, periph.astype(h.dtype)], axis=-1)
    return _head(params, h, _n_fc(params))
