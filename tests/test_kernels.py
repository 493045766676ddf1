"""Per-kernel validation: shape/dtype sweeps, assert_allclose against the
pure-jnp ref.py oracles (kernels run in interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AnalogConfig
from repro.configs.rram_ps32 import CASE_A, CASE_B
from repro.kernels.emulator_block.emulator_block import STEP_ROWS


# --------------------------------------------------------------------------- #
# xbar_mac
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,K,N", [(128, 128, 128), (256, 384, 128),
                                   (128, 512, 256), (64, 64, 64),
                                   # non-divisible shapes: pad-and-slice path
                                   (100, 70, 130), (65, 64, 63)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_xbar_mac(B, K, N, dtype):
    from repro.kernels.xbar_mac import xbar_mac
    from repro.kernels.xbar_mac.ref import xbar_mac_ref
    key = jax.random.PRNGKey(B + K + N)
    v = jax.random.uniform(key, (B, K), dtype, maxval=0.2)
    g = jax.random.uniform(jax.random.fold_in(key, 1), (K, N), dtype,
                           minval=1e-6, maxval=1e-4)
    out = xbar_mac(v, g, block_b=64, block_n=64, block_k=64)
    ref = xbar_mac_ref(v, g)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------- #
# flash_attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,H,S,D", [(2, 2, 256, 64), (1, 4, 128, 128),
                                     (2, 1, 512, 32)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, S, D, causal, window, dtype):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    key = jax.random.PRNGKey(S + D)
    q = jax.random.normal(key, (B, H, S, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, S, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, S, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=128, block_kv=128)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------- #
# linear_scan
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("B,S,D", [(2, 256, 512), (1, 128, 1024), (4, 512, 64)])
@pytest.mark.parametrize("with_h0", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_linear_scan(B, S, D, with_h0, dtype):
    from repro.kernels.linear_scan import linear_scan
    from repro.kernels.linear_scan.ref import linear_scan_ref
    key = jax.random.PRNGKey(S)
    a = jax.random.uniform(key, (B, S, D), dtype, minval=0.5, maxval=0.999)
    b = jax.random.normal(jax.random.fold_in(key, 1), (B, S, D), dtype) * 0.1
    h0 = (jax.random.normal(jax.random.fold_in(key, 2), (B, D), dtype)
          if with_h0 else None)
    h, h_last = linear_scan(a, b, h0, block_d=64, block_s=64)
    hr, hr_last = linear_scan_ref(a.astype(jnp.float32),
                                  b.astype(jnp.float32),
                                  None if h0 is None else h0.astype(jnp.float32))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h_last, np.float32),
                               np.asarray(hr_last, np.float32),
                               rtol=tol, atol=tol)


# --------------------------------------------------------------------------- #
# emulator_block_unified (ONE kernel, every device corner)
# --------------------------------------------------------------------------- #
# The kernel evaluates apply_blocklast's math in its own 2-d layout: the
# zero-voltage projection is summed per window position, the conv/FC
# stack runs as block-diagonal contractions, and CELU goes through exp
# (Mosaic has no expm1).  So the two agree to f32 rounding, not bitwise:
# measured |diff| <= 4e-6 on outputs of magnitude ~3.
UNIFIED_F32_TOL = dict(rtol=1e-5, atol=1e-5)


def _unified_params(geom, n_periph=2, seed=5):
    """The Conv4Xbar net's params the unified fixture is built from."""
    from repro.core import conv4xbar
    from repro.models.common import init_params
    return init_params(jax.random.PRNGKey(seed),
                       conv4xbar.conv4xbar_schema(geom, n_periph=n_periph))


def _unified_fixture(geom, n_periph=2, NB=2, NO=3, M=6, seed=5):
    """aux/g_norm/pre + drive tensors for the unified serving kernel."""
    from repro.core import conv4xbar
    key = jax.random.PRNGKey(seed)
    params = _unified_params(geom, n_periph, seed)
    aux = conv4xbar.blocklast_weights(params, geom)
    D, H, W = geom.tiles, geom.rows, geom.cols
    g = jax.random.uniform(jax.random.fold_in(key, 1), (NB, NO, D, H, W))
    pre = conv4xbar.blocklast_precompute(aux, g)
    u = jax.random.uniform(jax.random.fold_in(key, 2), (M, NB, D, H))
    pos = (jax.random.uniform(jax.random.fold_in(key, 3),
                              (M, NB, D, H)) > 0.5).astype(jnp.float32)
    return aux, g, pre, u, pos


@pytest.mark.parametrize("geom", [CASE_A, CASE_B], ids=lambda g: g.name)
# blocks a step (rounded up to 8) and output groups: one tile of 8 padded
# from 3, and two tiles of 8, the last padded from 2
@pytest.mark.parametrize("block_n,NO", [(4, 3), (8, 10)])
def test_emulator_block_unified_ideal_bitwise(geom, block_n, NO):
    """Ideal corner: the fused kernel (interpret mode) matches the chunked
    XLA fast path within UNIFIED_F32_TOL at any block tiling (the
    output-group axis is padded to a whole number of tiles and sliced
    back)."""
    from repro.core import conv4xbar
    from repro.kernels.emulator_block.emulator_block import (
        emulator_block_unified_pallas)
    aux, g, pre, u, pos = _unified_fixture(geom, NO=NO)
    ref = conv4xbar.apply_blocklast(aux, pre, u, pos, chunk=3)
    out = emulator_block_unified_pallas(aux, g, u, pos, block_n=block_n,
                                        interpret=True)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **UNIFIED_F32_TOL)


@pytest.mark.parametrize("geom", [CASE_A, CASE_B], ids=lambda g: g.name)
# M = 3 pads the rows of a launch; NO = 2 and 3 pad the output groups to
# a whole block tile
@pytest.mark.parametrize("M,NB,NO", [(4, 2, 3), (3, 1, 2)])
@pytest.mark.parametrize("corner", ["ideal", "flat_shift"])
def test_emulator_block_unified_matches_paper_conv(geom, M, NB, NO, corner):
    """The serving kernel (interpret mode) against the paper-faithful
    ``conv4xbar.apply`` (the package's ref.py oracle) of the materialized
    (V, G) channel stack of each rail -- drives ``u * pos`` and
    ``u * (1 - pos)`` -- with the
    peripheral vector ``(1, 0, *sfeat)`` that ``block_outputs`` feeds a
    conditioned net; the conditioned corner reaches the kernel as the fc0
    shift ``sfeat @ f0_scen``.  Within UNIFIED_F32_TOL on the CPU
    (measured |diff| <= 1.7e-6 on outputs of magnitude ~2); on the chip,
    where the TPU's exp differs by a few ulps, chip_smoke.py holds the
    same comparison to 1e-4."""
    from repro.kernels.emulator_block.emulator_block import (
        emulator_block_unified_pallas)
    from repro.kernels.emulator_block.ref import conv4xbar_apply_ref
    from repro.nonideal import N_SCENARIO_FEATURES
    aux, g, _, u, pos = _unified_fixture(
        geom, n_periph=2 + N_SCENARIO_FEATURES, NB=NB, NO=NO, M=M, seed=7)
    params = _unified_params(geom, 2 + N_SCENARIO_FEATURES, seed=7)
    sfeat = (jnp.linspace(-0.5, 0.5, N_SCENARIO_FEATURES)
             if corner == "flat_shift" else jnp.zeros(N_SCENARIO_FEATURES))
    shift = sfeat @ aux["f0_scen"] if corner == "flat_shift" else None
    out = emulator_block_unified_pallas(aux, g, u, pos, shift=shift,
                                        interpret=True)
    D, H, W = geom.tiles, geom.rows, geom.cols
    shp = (M, NB, NO, D, H, W)

    def paper(v):
        xv = jnp.broadcast_to(v[:, :, None, :, :, None], shp)
        xg = jnp.broadcast_to(g[None], shp)
        x = jnp.stack([xv, xg], axis=3).reshape(-1, 2, D, H, W)
        periph = jnp.concatenate(
            [jnp.ones((x.shape[0], 1)), jnp.zeros((x.shape[0], 1)),
             jnp.broadcast_to(sfeat, (x.shape[0], N_SCENARIO_FEATURES))],
            axis=-1)
        return conv4xbar_apply_ref(params, x, periph)

    with jax.default_matmul_precision("highest"):
        ref = jnp.stack([paper(u * pos), paper(u * (1.0 - pos))])
    assert out.shape == ref.shape == (2, M * NB * NO, geom.outputs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **UNIFIED_F32_TOL)


@pytest.mark.parametrize("geom", [CASE_A, CASE_B], ids=lambda g: g.name)
# two block tiles of 8 per block group, or one of 16 padded from 10
@pytest.mark.parametrize("block_n", [8, 16])
# one row, one row tile, and (STEP_ROWS // 8 + 3) several row tiles with
# the last one padded
@pytest.mark.parametrize("M", [1, 4, 6, STEP_ROWS // 8 + 3])
def test_emulator_block_unified_row_tiles(geom, block_n, M):
    """Rows that share a grid step, and so its conductance-only pass, read
    as they would alone: under a block-indexed scenario shift the kernel
    matches the XLA path, and each row matches an M = 1 launch of that
    row's drive, within UNIFIED_F32_TOL."""
    from repro.core import conv4xbar
    from repro.kernels.emulator_block.emulator_block import (
        emulator_block_unified_pallas)
    from repro.nonideal import N_SCENARIO_FEATURES
    NB, NO = 2, 10
    aux, g, pre, u, pos = _unified_fixture(
        geom, n_periph=2 + N_SCENARIO_FEATURES, NB=NB, NO=NO, M=M)
    feats = jax.random.uniform(jax.random.PRNGKey(4),
                               (NB * NO, N_SCENARIO_FEATURES))
    shift = feats @ aux["f0_scen"]
    launch = lambda uu, pp: emulator_block_unified_pallas(
        aux, g, uu, pp, shift=shift, block_n=block_n, interpret=True)
    out = launch(u, pos)
    ref = conv4xbar.apply_blocklast(aux, pre, u, pos, chunk=2,
                                    fc0_shift=shift)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **UNIFIED_F32_TOL)
    alone = jax.vmap(lambda uu, pp: launch(uu[None], pp[None]))(u, pos)
    rows = out.reshape(2, M, NB * NO, -1).transpose(1, 0, 2, 3)
    np.testing.assert_allclose(np.asarray(rows), np.asarray(alone),
                               **UNIFIED_F32_TOL)


# rows that share one conductance pass; the share of kron(I_G, w)'s
# 128x128 tiles that stage 1 pushes, at geometry A's G = 32, C0 = 16, O1 = 8;
# the tail's rail x bitline pieces a pass, four 32-lane pieces
@pytest.mark.parametrize("name,value", [
    ("emulator_kernel_rows_per_pass", 4),
    ("emulator_kernel_stage1_tile_share", 0.5),
    ("emulator_kernel_tail_pieces_per_pass", 4)])
def test_emulator_kernel_rows_per_pass_gauge(name, value):
    """With telemetry on, a launch records its gauges (set while tracing,
    so the output is unchanged); with it off, nothing is recorded."""
    from repro.kernels.emulator_block.emulator_block import (
        emulator_block_unified_pallas)
    from repro.obs import OBS
    aux, g, pre, u, pos = _unified_fixture(CASE_A, M=4)
    launch = lambda: np.asarray(emulator_block_unified_pallas(
        aux, g, u, pos, block_n=8, interpret=True))
    assert not OBS.enabled                        # suite default
    OBS.reset()
    off = launch()
    assert name not in OBS.snapshot()["metrics"]
    OBS.enable()
    try:
        on = launch()
        met = OBS.snapshot()["metrics"][name]
    finally:
        OBS.reset()
        OBS.disable()
    (s,) = met["series"]
    assert met["kind"] == "gauge"
    assert s["labels"] == {"m": "4", "nb": "2", "no": "3"}
    assert s["value"] == value
    np.testing.assert_array_equal(on, off)


def _stage1_shape(geom):
    """(G, C0, O1, w1k) of a geometry's stage 1: G row groups of C0
    channels in, O1 out, one (C0, O1) weight per window position."""
    from repro.core import conv4xbar
    from repro.models.common import init_params
    params = init_params(jax.random.PRNGKey(0),
                         conv4xbar.conv4xbar_schema(geom, n_periph=2))
    w1k = conv4xbar.blocklast_weights(params, geom)["w1k"]
    k1, C0, O1 = w1k.shape
    return geom.rows // k1, C0, O1, w1k


@pytest.mark.parametrize("case,G,C0,O1,gc,share", [
    (CASE_A.name, None, None, None, 16, 0.5),
    (CASE_B.name, None, None, None, 16, 0.5),
    # Gc * O1 never a multiple of 128 below G: one contraction
    ("O1 = 8, G = 6", 6, 16, 8, 6, 1.0),
    # Gc * C0 reaches a multiple of 128 only at G itself
    ("C0 = 12, G = 32", 32, 12, 8, 32, 1.0)])
def test_stage1_chunk(case, G, C0, O1, gc, share):
    """Stage 1's lane chunk is the fewest row groups that divide G and
    fill whole 128-lane tiles on both sides, else G; the share of the
    full weight's tiles it pushes follows."""
    from repro.kernels.emulator_block.emulator_block import (
        stage1_chunk, stage1_tile_share)
    if G is None:
        geom = {CASE_A.name: CASE_A, CASE_B.name: CASE_B}[case]
        G, C0, O1, _ = _stage1_shape(geom)
        assert (G, C0, O1) == (32, 16, 8)
    assert stage1_chunk(G, C0, O1) == gc
    assert stage1_tile_share(G, C0, O1) == share


@pytest.mark.parametrize("geom", [CASE_A, CASE_B], ids=lambda g: g.name)
def test_stage1_chunk_weight_is_the_diagonal_block(geom):
    """The chunk weight kron(I_Gc, w) is each diagonal block of the full
    kron(I_G, w), and every 128x128 tile of the full weight outside those
    blocks -- the tiles stage 1 no longer pushes -- is exactly zero."""
    from repro.kernels.emulator_block.emulator_block import (
        _kron_eye, stage1_chunk, stage1_tile_share)
    G, C0, O1, w1k = _stage1_shape(geom)
    gc = stage1_chunk(G, C0, O1)
    ci, co = gc * C0, gc * O1
    for kk in range(w1k.shape[0]):
        full = np.asarray(_kron_eye(G, w1k[kk]))
        chunk = np.asarray(_kron_eye(gc, w1k[kk]))
        assert chunk.shape == (ci, co)
        kept = np.zeros(full.shape, bool)
        for c in range(G // gc):
            np.testing.assert_array_equal(
                full[c * ci:(c + 1) * ci, c * co:(c + 1) * co], chunk)
            kept[c * ci:(c + 1) * ci, c * co:(c + 1) * co] = True
        assert np.all(full[~kept] == 0.0)
        tiles = kept.reshape(full.shape[0] // 128, 128,
                             full.shape[1] // 128, 128).any(axis=(1, 3))
        assert tiles.mean() == stage1_tile_share(G, C0, O1)
        nonzero = (full != 0).reshape(tiles.shape[0], 128,
                                      tiles.shape[1], 128).any(axis=(1, 3))
        assert np.all(tiles[nonzero])


def _tail_fixture(geom):
    """aux of a geometry's net, and (D, G, W, kw) of its tail."""
    from repro.core import conv4xbar
    from repro.models.common import init_params
    params = init_params(jax.random.PRNGKey(0),
                         conv4xbar.conv4xbar_schema(geom, n_periph=2))
    aux = conv4xbar.blocklast_weights(params, geom)
    G = geom.rows // aux["w1k"].shape[0]
    return aux, geom.tiles, G, geom.cols, aux["wstage"][2]


@pytest.mark.parametrize("case,width,W,kw,pieces", [
    # both geometries: 32-lane pieces, four a pass (A: all of a tile's
    # pieces; B: 16 pieces a tile d in 4 passes)
    (CASE_A.name, None, None, None, 4),
    (CASE_B.name, None, None, None, 4),
    # two fit: half a window of both rails, a whole window of one rail
    ("width 48", 48, 2, 2, 2),
    # wider than 64 lanes: one piece a pass, the unpacked schedule
    ("width 80", 80, 2, 2, 1),
    ("width 160", 160, 8, 2, 1),
    # three fit, but a pass of 3 would split a window: 2
    ("width 40, W 3", 40, 3, 2, 2)])
def test_tail_pieces_per_pass(case, width, W, kw, pieces):
    """Pieces a tail pass carries: the most that fit 128 lanes, divide
    the 2 * W pieces, and hold whole windows of both rails or divide
    one."""
    from repro.kernels.emulator_block.emulator_block import (
        tail_pieces_per_pass)
    if width is None:
        geom = {CASE_A.name: CASE_A, CASE_B.name: CASE_B}[case]
        aux, _, G, W, kw = _tail_fixture(geom)
        (w2, _, k2), (w3, _, k3) = aux["hstages"][1:]
        width = max(G // k2 * w2.shape[1], G // k2 // k3 * w3.shape[1])
        assert width == 32
    assert tail_pieces_per_pass(width, W, kw) == pieces


def _assert_blocks(a, rows, cols, blocks):
    """``a`` holds each ``blocks[(i, j)]`` at block row i and column j of
    ``rows`` x ``cols`` blocks, and exact zeros everywhere else."""
    a = np.asarray(a)
    kept = np.zeros(a.shape, bool)
    for (i, j), b in blocks.items():
        sl = np.s_[i * rows:(i + 1) * rows, j * cols:(j + 1) * cols]
        np.testing.assert_array_equal(a[sl], np.asarray(b, np.float32))
        kept[sl] = True
    assert np.all(a[~kept] == 0.0)


@pytest.mark.parametrize("geom", [CASE_A, CASE_B], ids=lambda g: g.name)
def test_tail_operands_are_block_placements(geom):
    """Each packed tail weight holds the per-piece weight on its blocks
    and zeros elsewhere: stage 2's copy i writes piece i's 32 lanes
    [32 i, 32 i + 32), stage 3 is kron(I_P, w3), the bitline-pair stage
    kron(I_2, ws) over both rails' windows, fc0 each window's tile rows
    in its rail's lanes, and the FC layers kron(I_2, w)."""
    from repro.kernels.emulator_block.emulator_block import (
        _kron_eye, tail_operands)
    aux, D, G, W, kw = _tail_fixture(geom)
    P, ops = tail_operands(aux, D, G, W)
    assert P == 4
    (w2p, b2p, w3p, b3p, wsp, wsb, f0p, f0b, f1, f1b, fl, flb) = ops
    (w2, b2, k2), (w3, b3, _) = aux["hstages"][1:]
    w2g = _kron_eye(G // k2, w2)                      # (256, 32)
    assert w2g.shape == (256, 32) and w2p.shape == (P, 256, P * 32)
    for i in range(P):
        _assert_blocks(w2p[i], 256, 32, {(0, i): w2g})
    _assert_blocks(w3p, 32, 32, {(i, i): w3 for i in range(P)})
    np.testing.assert_array_equal(b2p[0], np.tile(b2, P * G // k2))
    np.testing.assert_array_equal(b3p[0], np.tile(b3, P))
    ws, wsb0, _ = aux["wstage"]
    assert wsp.shape == (1, P * 32, 64)
    _assert_blocks(wsp[0], 2 * 32, 32, {(0, 0): ws, (1, 1): ws})
    np.testing.assert_array_equal(wsb[0], np.tile(wsb0, 2))
    (f0, f0b0), (fw1, fb1), (fwl, fbl) = aux["fcs"]
    wo_n = W // kw
    f0 = np.asarray(f0).reshape(D, wo_n, 32, 32)
    assert f0p.shape == (D * wo_n, 64, 64)
    for d in range(D):
        for wo in range(wo_n):
            _assert_blocks(f0p[d * wo_n + wo], 32, 32,
                           {(0, 0): f0[d, wo], (1, 1): f0[d, wo]})
    np.testing.assert_array_equal(f0b[0], np.tile(f0b0, 2))
    _assert_blocks(f1, 32, 16, {(0, 0): fw1, (1, 1): fw1})
    _assert_blocks(fl, geom.outputs, 16, {(0, 0): fwl.T, (1, 1): fwl.T})
    np.testing.assert_array_equal(f1b[0], np.tile(fb1, 2))
    np.testing.assert_array_equal(flb[:, 0], np.tile(fbl, 2))


@pytest.mark.parametrize("geom", [CASE_A, CASE_B], ids=lambda g: g.name)
# one piece a pass (the unpacked schedule: a bitline-pair contraction
# reads two passes) and two (one rail's window a pass)
@pytest.mark.parametrize("pieces", [1, 2])
def test_emulator_block_unified_any_tail_packing(geom, pieces,
                                                 monkeypatch):
    """The kernel reads the same at every packing of the tail, not only
    the four pieces a pass both geometries take: forced to 1 or 2, it
    matches the XLA path within UNIFIED_F32_TOL under a block-indexed
    shift."""
    from repro.core import conv4xbar
    from repro.kernels.emulator_block import emulator_block as eb
    from repro.nonideal import N_SCENARIO_FEATURES
    aux, g, pre, u, pos = _unified_fixture(
        geom, n_periph=2 + N_SCENARIO_FEATURES, M=3)
    shift = jax.random.uniform(jax.random.PRNGKey(4),
                               (2 * 3, N_SCENARIO_FEATURES)) @ aux["f0_scen"]
    monkeypatch.setattr(eb, "tail_pieces_per_pass", lambda *_: pieces)
    assert eb.tail_operands(aux, geom.tiles, 32, geom.cols)[0] == pieces
    out = eb.emulator_block_unified_pallas(aux, g, u, pos, shift=shift,
                                           block_n=8, interpret=True)
    ref = conv4xbar.apply_blocklast(aux, pre, u, pos, chunk=2,
                                    fc0_shift=shift)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **UNIFIED_F32_TOL)


def test_emulator_block_unified_conditioned():
    """Conditioned corner: the scenario epilogue (fc0 shift, flat and
    per-tile) matches the XLA path, and the all-zero feature encoding
    reproduces the ideal corner of the same net exactly -- one compiled
    kernel per shape serves every corner."""
    from repro.core import conv4xbar
    from repro.kernels.emulator_block.emulator_block import (
        emulator_block_unified_pallas)
    from repro.nonideal import N_SCENARIO_FEATURES
    aux, g, pre, u, pos = _unified_fixture(
        CASE_A, n_periph=2 + N_SCENARIO_FEATURES)
    sfeat = jnp.linspace(-0.5, 0.5, N_SCENARIO_FEATURES)
    tiled = jax.random.uniform(jax.random.PRNGKey(1),
                               (2 * 3, N_SCENARIO_FEATURES))
    for shift in (sfeat @ aux["f0_scen"], tiled @ aux["f0_scen"]):
        ref = conv4xbar.apply_blocklast(aux, pre, u, pos, chunk=2,
                                        fc0_shift=shift)
        out = emulator_block_unified_pallas(aux, g, u, pos, shift=shift,
                                            block_n=4, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **UNIFIED_F32_TOL)
    # zero features == no epilogue == the plain ideal evaluation, bitwise
    z = jnp.zeros((N_SCENARIO_FEATURES,)) @ aux["f0_scen"]
    out_z = emulator_block_unified_pallas(aux, g, u, pos, shift=z,
                                          block_n=4, interpret=True)
    out_n = emulator_block_unified_pallas(aux, g, u, pos, shift=None,
                                          block_n=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(out_z), np.asarray(out_n))


def test_emulator_block_unified_nonideal_vs_block_tensor():
    """Non-ideal corner, end to end: the unified-kernel fast path under a
    stressed scenario (perturbed conductances + conditioning features)
    agrees with the block-tensor reference path within fp32 tolerance."""
    from repro.configs.base import AnalogConfig
    from repro.core.analog import AnalogExecutor
    from repro.core import conv4xbar
    from repro.models.common import init_params
    from repro.nonideal import N_SCENARIO_FEATURES, get_scenario
    key = jax.random.PRNGKey(9)
    params = init_params(key, conv4xbar.conv4xbar_schema(
        CASE_A, n_periph=2 + N_SCENARIO_FEATURES))
    w = jax.random.normal(key, (70, 3)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 70)) * 0.5
    kw = dict(acfg=AnalogConfig(backend="emulator"), geom=CASE_A,
              emulator_params=params)
    outs = []
    for exkw in (dict(fast_path=False), dict(use_pallas=True)):
        ex = AnalogExecutor(**kw, **exkw)
        ex.deploy(scenario=get_scenario("stressed"),
                  key=jax.random.PRNGKey(2))
        outs.append(np.asarray(ex.matmul(x, w, "t")))
    np.testing.assert_allclose(outs[1], outs[0], rtol=2e-4, atol=1e-5)


def test_emulator_block_unified_dispatcher_fallback_bitwise():
    """The dispatcher's two routes off the TPU (interpreted kernel /
    chunked XLA) agree within UNIFIED_F32_TOL, and the XLA route is
    bit-identical whether it is handed the precompute or derives it."""
    from repro.kernels.emulator_block import emulator_block_unified
    aux, g, pre, u, pos = _unified_fixture(CASE_A)
    y_xla = emulator_block_unified(aux, g, u, pos, use_pallas=False)
    y_pre = emulator_block_unified(aux, g, u, pos, pre=pre,
                                   use_pallas=False)
    y_pl = emulator_block_unified(aux, g, u, pos, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(y_pre), np.asarray(y_xla))
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_xla),
                               **UNIFIED_F32_TOL)


def test_unified_kernel_compile_once_across_corners():
    """Corner swaps through the deployed forward recompile NOTHING with the
    fused kernel on the fast path: scenario features ride the precomputed
    shift operand, perturbed conductances ride pre[...] -- all traced
    leaves of one executable."""
    from repro.configs.base import AnalogConfig
    from repro.core.analog import AnalogExecutor
    from repro.core import conv4xbar
    from repro.models.common import init_params
    from repro.nonideal import N_SCENARIO_FEATURES, get_scenario
    key = jax.random.PRNGKey(11)
    params = init_params(key, conv4xbar.conv4xbar_schema(
        CASE_A, n_periph=2 + N_SCENARIO_FEATURES))
    w = jax.random.normal(key, (70, 3)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 70)) * 0.5
    ex = AnalogExecutor(acfg=AnalogConfig(backend="emulator"), geom=CASE_A,
                        emulator_params=params, use_pallas=True)
    outs = [np.asarray(ex.matmul(x, w, "t"))]                  # ideal
    fn = ex._fns["t"][2]
    ex.deploy(scenario=get_scenario("stressed"), key=jax.random.PRNGKey(3))
    outs.append(np.asarray(ex.matmul(x, w, "t")))              # corner
    ex.deploy(age=2.592e6)
    outs.append(np.asarray(ex.matmul(x, w, "t")))              # age
    assert ex._fns["t"][2] is fn
    assert fn._cache_size() == 1                               # ONE compile
    for a, b in zip(outs, outs[1:]):
        assert not np.array_equal(a, b)
