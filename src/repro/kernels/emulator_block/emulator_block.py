"""Pallas TPU kernel: the Conv4Xbar emulator's serving math, fused in VMEM.

At system level the emulator runs over THOUSANDS of crossbar blocks per
layer (every weight tile of every projection), each a small conv + FC
stack, for both voltage rails.  ``emulator_block_unified_pallas`` is one
``pallas_call`` per matmul, for every device corner: each grid step
holds one tile of crossbar blocks and one tile of batch rows in VMEM and
evaluates both rails through the whole network without touching HBM in
between; the emulator's weights (a few KB, as block-diagonal
``kron(I, w)`` contractions) stay resident across the grid.

Tiling: grid (block tiles, row tiles); ``row_tile`` sizes the row tile
and ``stage1_chunk`` the lane chunks of stage 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import OBS


def _celu(x):
    """CELU (alpha 1) through ``exp``: Mosaic has no ``expm1`` lowering,
    so the negative branch carries an absolute error of about one f32
    ulp of 1 where ``jax.nn.celu`` is relative-exact."""
    return jnp.maximum(x, 0.0) + (jnp.exp(jnp.minimum(x, 0.0)) - 1.0)


def _exact_dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """f32 contraction at full precision (on the TPU's MXU a default-
    precision f32 dot rounds its operands to bf16)."""
    return jax.lax.dot_general(a, b, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


_NT = (((1,), (1,)), ((), ()))             # a @ b.T


def _unified_kernel(*refs, D: int, W: int, G: int, k1: int, kw: int,
                    P: int, n_chunk: int, n_tail: int, n_fc: int):
    """Grid step (block tile j, row tile i): BOTH rails of the dual-rail
    delta factorization and the whole conv/FC stack for ``bn`` crossbar
    blocks and ``bm`` batch rows, in VMEM.

    Layout: blocks ride the sublane dim, and each (window position kk,
    tile d, bitline w) piece of a block keeps its row groups and channels
    flattened in the lanes, ``(g, c)``.  Every stage is then a 2-d
    contraction against a block-diagonal ``kron(I, w)`` weight built by
    the wrapper, and every slice is a static index of a ref's leading
    dim -- the forms Mosaic lowers.  Stage 1 contracts ``n_chunk`` lane
    chunks of row groups one at a time against the weight of one chunk
    (``stage1_chunk``), so no MXU pass multiplies a 128x128 tile of the
    full ``kron(I_G, w)`` that lies off its diagonal and is all zero.
    The stage-0 precompute (``g0``, its zero-voltage response and
    stage-1 projection) is evaluated here from the normalized
    conductances rather than read from HBM, once per step and shared by
    the ``bm`` rows: from the drive on, the rows are stacked in the
    sublanes, ``(m, block)``, so each stage is one contraction over
    ``bm * bn`` rows.

    The tail packs ``P`` rail x bitline pieces side by side in the lanes
    (``tail_pieces_per_pass``), ordered (bitline window wo, rail r,
    bitline j of the window): each piece's stage-2 contraction writes its
    own lanes of its pass, and each later stage is one contraction of
    the pass against ``kron(I_P, w)``.  The bitline-pair stage reads
    ``n_in`` passes and writes ``win`` (wo, r) windows side by side, and
    fc0 adds each window into its rail's lanes of one accumulator that
    holds both rails, as do the FC layers after it."""
    (u_ref, pos_ref, gn_ref, sh_ref, ev_ref, eg_ref, b0_ref, w1_ref, b1_ref,
     em_ref) = refs[:10]
    idx = 10
    tail = []
    for _ in range(n_tail):
        tail.append((refs[idx], refs[idx + 1]))
        idx += 2
    ws_ref, wsb_ref, f0_ref, f0b_ref = refs[idx:idx + 4]
    idx += 4
    fcs = []
    for _ in range(n_fc - 1):
        fcs.append((refs[idx], refs[idx + 1]))
        idx += 2
    o_ref = refs[idx]
    bm, bn = u_ref.shape[3], gn_ref.shape[1]

    def by_block(a):
        """(bn, L), one row per block -> (bm*bn, L), the same for each m."""
        return jnp.broadcast_to(a[None], (bm,) + a.shape).reshape(
            bm * bn, a.shape[1])

    def by_row(a):
        """(bm, L), one row per batch row -> (bm*bn, L), for each block."""
        return jnp.broadcast_to(a[:, None], (bm, bn, a.shape[1])).reshape(
            bm * bn, a.shape[1])

    ev, eg, em = ev_ref[...], eg_ref[...], em_ref[...]
    b0, b1 = b0_ref[...], b1_ref[...]
    w1 = [w1_ref[kk] for kk in range(k1)]
    (w2_ref, b2_ref), rest = tail[0], tail[1:]
    span = max(P, 2 * kw)        # pieces: whole passes, whole windows
    n_in = max(1, kw // P)       # passes one bitline-pair pass reads
    win = max(1, P // kw)        # (wo, r) windows it writes
    n_ws = 2 * W // (win * kw)   # bitline-pair passes a tile d

    cin = w1[0].shape[0]

    def stage1(v, g0, w):
        """Stage 1 of one piece, a lane chunk of row groups at a time:
        its (g, c) lanes in, the matching (g, o1) lanes out, the chunk
        boundaries on whole lane tiles.  Returns the conductance-only
        projection ``y0`` (bn rows) and the drive-dependent ``t``
        (bm*bn rows)."""
        c0 = _celu(g0)
        y0, t = [], []
        for c in range(n_chunk):
            cl = slice(c * cin, (c + 1) * cin)
            y0.append(_exact_dot(c0[:, cl], w))
            t.append(_exact_dot(
                _celu(by_row(v[:, cl]) + by_block(g0[:, cl]))
                - by_block(c0[:, cl]), w))
        if n_chunk == 1:
            return y0[0], t[0]
        return jnp.concatenate(y0, axis=1), jnp.concatenate(t, axis=1)

    def rails(d, w, v0, mk):
        """Stage 1 of bitline w of tile d: both rails' activations."""
        y0, t_full, t_pos = b1, None, None
        for kk in range(k1):
            lanes = pl.ds((kk * W + w) * G, G)
            g0 = _exact_dot(gn_ref[d, :, lanes], eg) + b0
            y1, t = stage1(v0[kk], g0, w1[kk])
            y0 = y0 + y1
            t_full = t if t_full is None else t_full + t
            tp = t * mk[kk]
            t_pos = tp if t_pos is None else t_pos + tp
        y0 = by_block(y0)
        return _celu(y0 + t_pos), _celu(y0 + t_full - t_pos)

    def tile(d, acc):
        # the wordline drive of the bm rows and their positive-rail mask,
        # expanded from row groups g to the (g, c) lanes of each piece
        v0 = [_exact_dot(u_ref[0, 0, kk * D + d], ev) for kk in range(k1)]
        mk = [by_row(_exact_dot(pos_ref[0, 0, kk * D + d], em))
              for kk in range(k1)]
        for s0 in range(0, 2 * W, span):
            passes = [None] * (span // P)
            for wo in range(s0 // (2 * kw), (s0 + span) // (2 * kw)):
                for j in range(kw):
                    for r, x in enumerate(rails(d, wo * kw + j, v0, mk)):
                        p = (2 * wo + r) * kw + j - s0
                        y = _exact_dot(x, w2_ref[p % P])
                        q = p // P
                        passes[q] = y if passes[q] is None else passes[q] + y
            h = []
            for x in passes:
                x = _celu(x + b2_ref[...])
                for wk_ref, bk_ref in rest:
                    x = _celu(_exact_dot(x, wk_ref[...]) + bk_ref[...])
                h.append(x)
            # bitline-pair stage, then this tile's rows of fc0
            for t in range(len(h) // n_in):
                s = wsb_ref[...]
                for i in range(n_in):
                    s = s + _exact_dot(h[t * n_in + i], ws_ref[i])
                acc = acc + _exact_dot(
                    _celu(s), f0_ref[d * n_ws + s0 // (win * kw) + t])
        return acc

    acc0 = by_block(jnp.broadcast_to(f0b_ref[...] + sh_ref[...],
                                     (bn, f0b_ref.shape[1])))
    x = jax.lax.fori_loop(0, D, tile, acc0)
    for fw_ref, fb_ref in fcs[:-1]:
        x = _exact_dot(_celu(x), fw_ref[...]) + fb_ref[...]
    # the last layer is contracted transposed, a row at a time,
    # (2 * n_out, bn): blocks land in the lanes, so the output is not
    # padded to 128 lanes, and with the rows leading the output block
    # the wrapper's first relayout of it is a bitcast
    fw_ref, fb_ref = fcs[-1]
    x = _celu(x)
    for m in range(bm):
        y = (_exact_dot(fw_ref[...], x[m * bn:(m + 1) * bn], _NT)
             + fb_ref[...])
        o_ref[0, 0, m] = y.astype(o_ref.dtype)


def _const_spec(arr):
    return pl.BlockSpec(arr.shape, lambda *_, nd=arr.ndim: (0,) * nd)


def _kron_eye(n: int, w: jax.Array) -> jax.Array:
    """Block-diagonal ``kron(I_n, w)``: one copy of ``w`` per row group."""
    return jnp.kron(jnp.eye(n, dtype=jnp.float32), w.astype(jnp.float32))


def _lane_place(i: int, n: int, w: jax.Array) -> jax.Array:
    """``w`` (K, N) in output lanes ``[i*N, (i+1)*N)`` of a (K, n*N)
    weight, zero elsewhere: column block i of n."""
    return jnp.kron(jnp.eye(1, n, i, dtype=jnp.float32),
                    w.astype(jnp.float32))


_LANES = 128                               # lanes of a vreg, of an MXU tile


def stage1_chunk(G: int, C0: int, O1: int) -> int:
    """Row groups one stage-1 contraction covers: the fewest that divide
    ``G`` and fill whole lane tiles on both sides, ``Gc * C0`` channels in
    and ``Gc * O1`` out -- so its weight ``kron(I_Gc, w)`` is the diagonal
    block of ``kron(I_G, w)`` that holds every nonzero tile -- else ``G``,
    one contraction over every group."""
    return next((gc for gc in range(1, G + 1)
                 if G % gc == 0 and gc * C0 % _LANES == 0
                 and gc * O1 % _LANES == 0), G)


def stage1_tile_share(G: int, C0: int, O1: int) -> float:
    """The 128x128 weight tiles stage 1 pushes through the MXU, over those
    of the full ``kron(I_G, w)``: 1.0 for one contraction over every
    group."""
    tiles = lambda n: -(-n * C0 // _LANES) * -(-n * O1 // _LANES)
    gc = stage1_chunk(G, C0, O1)
    return G // gc * tiles(gc) / tiles(G)


def tail_pieces_per_pass(width: int, W: int, kw: int) -> int:
    """Rail x bitline pieces, ``width`` lanes each, that one pass of the
    tail stages carries side by side: the most that fit ``_LANES`` lanes
    and divide the ``2 * W`` pieces into whole passes, where a pass holds
    whole windows of ``kw`` bitlines in both rails (a multiple of
    ``2 * kw`` pieces) or divides one window -- at worst 1, a pass a
    piece."""
    return next((p for p in range(min(_LANES // width, 2 * W), 0, -1)
                 if 2 * W % p == 0 and (p % (2 * kw) == 0 or kw % p == 0)),
                1)


def tail_operands(aux: dict, D: int, G: int, W: int) -> tuple[int, list]:
    """The tail's pieces per pass ``P`` and its weights, packed for the
    kernel and in its operand order, every weight with its bias row:

    - stage 2: ``(P, G*O1, P*n)``, copy i holding the stage's
      ``kron(I_g, w)`` (n output lanes) in lanes ``[i*n, (i+1)*n)``, so
      piece i of a pass writes its own lanes;
    - each later stage: ``kron(I_{P*g}, w)``, every piece of a pass alike;
    - the bitline-pair stage: ``kron(I_win, ws)``, split by rows into the
      ``n_in`` passes one of its contractions reads, for ``win`` (wo, r)
      windows a contraction;
    - fc0: ``(D * n_ws, win*Cw, 2*F0)``, one per tile d and bitline-pair
      contraction, window (wo, r)'s rows ``f0[d, wo]`` in rail r's lanes;
    - the FC layers after it: ``kron(I_2, w)``, both rails; the last
      transposed, ``kron(I_2, w.T)``."""
    f32 = lambda a: a.astype(jnp.float32)
    g, tail = G, []
    for wk, b, k in aux["hstages"][1:]:
        g //= k
        tail.append((wk, b, g))
    assert g == 1, "row stack must reduce every wordline group"
    wst_w, wst_b, kw = aux["wstage"]
    P = tail_pieces_per_pass(max(g * wk.shape[1] for wk, _, g in tail),
                             W, kw)
    win, n_in = max(1, P // kw), max(1, kw // P)
    (w2, b2, g), rest = tail[0], tail[1:]
    ops = [jnp.stack([_lane_place(i, P, _kron_eye(g, w2))
                      for i in range(P)]),
           jnp.tile(f32(b2), P * g)[None]]
    for wk, b, g in rest:
        ops += [_kron_eye(P * g, wk), jnp.tile(f32(b), P * g)[None]]
    fcs = aux["fcs"]
    Cw, F0 = wst_w.shape[1], fcs[0][0].shape[1]
    f0 = f32(fcs[0][0]).reshape(D, W // kw, Cw, F0)
    ops += [_kron_eye(win, wst_w).reshape(n_in, -1, win * Cw),
            jnp.tile(f32(wst_b), win)[None],
            jnp.stack([jnp.concatenate([_lane_place(i % 2, 2, f0[d, i // 2])
                                        for i in range(t, t + win)])
                       for d in range(D) for t in range(0, 2 * W // kw, win)]),
            jnp.tile(f32(fcs[0][1]), 2)[None]]
    for fw, fb in fcs[1:-1]:
        ops += [_kron_eye(2, fw), jnp.tile(f32(fb), 2)[None]]
    # joined, not tiled: a tile of a one-element bias is a broadcast that
    # XLA may merge with the kernel's own when interpreted
    fb = f32(fcs[-1][1])
    ops += [_kron_eye(2, fcs[-1][0].T), jnp.concatenate([fb, fb])[:, None]]
    return P, ops


# crossbar blocks a grid step: the tiling every served launch has run at,
# and the one the chip's bundle dumps and timings measured
BLOCK_N = 128
# stacked rows (batch rows x crossbar blocks) one grid step of the unified
# kernel holds: at the served widths its working set then fits the v5e's
# default scoped VMEM (tests/test_tpu_compile.py)
STEP_ROWS = 512


def row_tile(M: int, bn: int) -> tuple[int, int]:
    """(bm, row tiles) for M batch rows at ``bn`` blocks a step: all M
    rows in one tile when ``M * bn`` fits ``STEP_ROWS``, else the fewest
    tiles that fit, of equal size, so the last pads M the least."""
    mt = -(-M // max(1, STEP_ROWS // bn))
    return -(-M // mt), mt


def emulator_block_unified_pallas(aux: dict, g_norm: jax.Array,
                                  u01: jax.Array, pos01: jax.Array, *,
                                  shift: jax.Array | None = None,
                                  block_n: int = BLOCK_N,
                                  interpret: bool = False) -> jax.Array:
    """One kernel launch per matmul, every corner on the TPU path.

    aux: ``conv4xbar.blocklast_weights`` tensors; g_norm: (NB, NO, D, H, W)
    normalized -- deployed, possibly perturbed -- conductances; u01/pos01:
    (M, NB, D, H) magnitude drive and positive-rail mask; shift: optional
    scenario epilogue ``sfeat @ aux["f0_scen"]`` -- ``(fc0_out,)``
    grid-constant for a whole-plan corner, or ``(NB*NO, fc0_out)``
    block-indexed for per-tile feature operands -- None = ideal, an exact
    zero add.  ``block_n`` crossbar blocks share one grid step (rounded
    to a multiple of 8; the output-group axis is zero-padded to a whole
    number of tiles and sliced back), and so do ``bm`` batch rows
    (``row_tile``: all M rows where they fit, the batch axis padded to
    whole row tiles otherwise), which share the step's conductance-only
    work.

    Numerics: the same math as ``conv4xbar.apply_blocklast``, associated
    differently -- the stage-1 projection of the zero-voltage response
    is summed per window position, and the conv/FC stack runs as
    block-diagonal contractions -- so the two agree to f32 rounding, not
    bitwise (tests/test_kernels.py states the tolerance).  Every
    contraction is f32 at ``Precision.HIGHEST``.
    Returns (2, M*NB*NO, O) rail block outputs, row-compatible with
    ``apply_blocklast``."""
    M, NB, D, H = u01.shape
    _, NO, _, _, W = g_norm.shape
    w1k = aux["w1k"]
    k1, C0, O1 = w1k.shape
    G = H // k1
    fcs = aux["fcs"]
    n_fc = len(fcs)
    n_out = fcs[-1][0].shape[1]
    F0 = fcs[0][0].shape[1]
    kw = aux["wstage"][2]

    bn = -(-min(block_n, NO) // 8) * 8
    NOp = -(-NO // bn) * bn
    nbt = NOp // bn                                   # block tiles per nb
    bm, mt = row_tile(M, bn)
    Mp = bm * mt
    Gc = stage1_chunk(G, C0, O1)

    # tiles d lead, blocks next, (kk, w, g) in the lanes.  The wrapper's
    # ops sit under two named scopes, so the device trace tells the
    # conductance relayout (``emu_layout_g``: a function of the deployed
    # g_norm alone) from the per-call drive, constant and output
    # relayouts (``emu_layout_io``) and from the kernel itself
    with jax.named_scope("emu_layout_g"):
        gn = g_norm.astype(jnp.float32).reshape(NB, NO, D, G, k1, W)
        if NOp != NO:
            gn = jnp.pad(gn, ((0, 0), (0, NOp - NO)) + ((0, 0),) * 4)
        gn = gn.transpose(2, 0, 1, 4, 5, 3).reshape(D, NB * NOp,
                                                    k1 * W * G)

    with jax.named_scope("emu_layout_io"):
        def drive(a):                           # -> (mt, NB, k1*D, bm, G)
            a = a.astype(jnp.float32)
            if Mp != M:
                a = jnp.pad(a, ((0, Mp - M),) + ((0, 0),) * 3)
            a = a.reshape(mt, bm, NB, D, G, k1).transpose(0, 2, 5, 3, 1, 4)
            return a.reshape(mt, NB, k1 * D, bm, G)

        # the shift in both rails' lanes
        tiled = shift is not None and shift.ndim == 2
        if shift is None:
            shift = jnp.zeros((1, 2 * F0), jnp.float32)
        elif shift.ndim == 1:
            shift = jnp.tile(shift, 2)[None]
        else:
            shift = shift.reshape(NB, NO, F0)
            if NOp != NO:
                shift = jnp.pad(shift, ((0, 0), (0, NOp - NO), (0, 0)))
            shift = jnp.tile(shift.reshape(NB * NOp, F0), (1, 2))

        f32 = lambda a: a.astype(jnp.float32)
        ev = _kron_eye(G, aux["w0v"][None])              # (G, G*C0)
        eg = _kron_eye(G, aux["w0g"][None])
        b0 = jnp.tile(f32(aux["b0"]), G)[None]           # (1, G*C0)
        w1big = jnp.stack([_kron_eye(Gc, w1k[kk]) for kk in range(k1)])
        b1 = jnp.tile(f32(aux["hstages"][0][1]), G)[None]
        em = _kron_eye(G, jnp.ones((1, O1), jnp.float32))  # mask->(g, o1)
        P, tail = tail_operands(aux, D, G, W)
        consts = [ev, eg, b0, w1big, b1, em] + tail
        du, dp, sh = drive(u01), drive(pos01), f32(shift)

    if OBS.enabled:
        labels = dict(m=str(M), nb=str(NB), no=str(NO))
        OBS.gauge("emulator_kernel_rows_per_pass",
                  "batch rows that share one conductance pass of the "
                  "unified emulator kernel (rows per grid step)",
                  **labels).set(bm)
        OBS.gauge("emulator_kernel_stage1_tile_share",
                  "128x128 weight tiles the unified emulator kernel's "
                  "stage 1 pushes, over those of the full kron(I_G, w)",
                  **labels).set(stage1_tile_share(G, C0, O1))
        OBS.gauge("emulator_kernel_tail_pieces_per_pass",
                  "rail x bitline pieces one MXU pass of the unified "
                  "emulator kernel's tail stages carries side by side",
                  **labels).set(P)

    blk = lambda j, i: (i, j // nbt, 0, 0, 0)
    in_specs = [
        pl.BlockSpec((1, 1, k1 * D, bm, G), blk),
        pl.BlockSpec((1, 1, k1 * D, bm, G), blk),
        pl.BlockSpec((D, bn, k1 * W * G), lambda j, i: (0, j, 0)),
        (pl.BlockSpec((bn, 2 * F0), lambda j, i: (j, 0))
         if tiled else _const_spec(shift)),
    ] + [_const_spec(c) for c in consts]

    out = pl.pallas_call(
        functools.partial(_unified_kernel, D=D, W=W, G=G, k1=k1, kw=kw,
                          P=P, n_chunk=G // Gc,
                          n_tail=len(aux["hstages"]) - 1, n_fc=n_fc),
        grid=(NB * nbt, mt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bm, 2 * n_out, bn),
                               lambda j, i: (i, j, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((mt, NB * nbt, bm, 2 * n_out, bn),
                                       jnp.float32),
        interpret=interpret,
        name="emulator_block_unified",
    )(du, dp, gn, sh, *consts)
    with jax.named_scope("emu_layout_io"):
        out = out.reshape(mt, NB, nbt, bm, 2, n_out, bn)
        out = out.transpose(4, 0, 3, 1, 2, 6, 5)
        out = out.reshape(2, Mp, NB, NOp, n_out)[:, :M, :, :NO]
        return out.reshape(2, M * NB * NO, n_out)
