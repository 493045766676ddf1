"""Crossbar geometry: mapping real-valued weight matrices onto tiled
differential 1T1R crossbar arrays, and building the (C, D, H, W) cell-feature
tensors the paper's emulator consumes.

Layout (matching paper Table 1 geometries):
  * a weight column j (output j) maps to a differential bitline pair
    (G+ holds w>0, G- holds -w<0), so W = 2 * outs_per_block columns/tile
  * the K input rows are split into tiles of `rows`; `tiles_per_block` tiles
    are accumulated *in analog* inside one computing block; remaining tiles
    go to further blocks summed digitally.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AnalogConfig
from repro.configs.rram_ps32 import BlockGeometry


def weights_to_conductance(w: jax.Array, acfg: AnalogConfig,
                           w_scale: jax.Array):
    """w: (K, N) real -> (g_pos, g_neg): (K, N) conductances in [g_min,g_max].

    w_scale: per-output (N,) or scalar normalization (max |w|)."""
    span = acfg.g_max - acfg.g_min
    wn = w / jnp.maximum(w_scale, 1e-12)
    g_pos = acfg.g_min + span * jnp.clip(wn, 0.0, 1.0)
    g_neg = acfg.g_min + span * jnp.clip(-wn, 0.0, 1.0)
    return g_pos, g_neg


def conductance_to_weights(g_pos, g_neg, acfg: AnalogConfig, w_scale):
    """Inverse mapping (exact for |wn| <= 1)."""
    span = acfg.g_max - acfg.g_min
    return (g_pos - g_neg) / span * w_scale


def pad_rows(x: jax.Array, rows: int, axis: int = 0) -> jax.Array:
    k = x.shape[axis]
    pad = (-k) % rows
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def tile_matrix(w: jax.Array, acfg: AnalogConfig) -> Tuple[jax.Array, jax.Array]:
    """(K, N) -> (T, rows, N) tiles of G+/G- with zero padding.

    Returns (g_pos_tiles, g_neg_tiles), each (T, rows, N)."""
    K, N = w.shape
    w_scale = jnp.max(jnp.abs(w))
    g_pos, g_neg = weights_to_conductance(w, acfg, w_scale)
    # zero weight -> both rails g_min (cancels differentially)
    g_pos = pad_rows(g_pos, acfg.rows)
    g_neg = pad_rows(g_neg, acfg.rows)
    T = g_pos.shape[0] // acfg.rows
    return (g_pos.reshape(T, acfg.rows, N), g_neg.reshape(T, acfg.rows, N))


def tile_inputs(v: jax.Array, acfg: AnalogConfig) -> jax.Array:
    """(B, K) in [0,1] -> (B, T, rows) wordline drive voltages."""
    B, K = v.shape
    v = pad_rows(v, acfg.rows, axis=1)
    T = v.shape[1] // acfg.rows
    return v.reshape(B, T, acfg.rows) * acfg.v_read


def build_block_tensor(v_tiles: jax.Array, gp: jax.Array, gn: jax.Array,
                       geom: BlockGeometry, out_slice) -> jax.Array:
    """Assemble the emulator input tensor X (B, C=2, D, H, W) for one block.

    v_tiles: (B, D, H) voltages; gp/gn: (D, H, n_out) conductances for the
    outputs in `out_slice` (n_out = geom.outputs). W interleaves (G+, G-)
    per output: W = 2 * n_out.
    """
    B, D, H = v_tiles.shape
    n_out = gp.shape[-1]
    # conductance channel: (D, H, W)
    g = jnp.stack([gp, gn], axis=-1).reshape(D, H, 2 * n_out)
    gch = jnp.broadcast_to(g[None], (B, D, H, 2 * n_out))
    vch = jnp.broadcast_to(v_tiles[..., None], (B, D, H, 2 * n_out))
    x = jnp.stack([vch, gch], axis=1)                 # (B, 2, D, H, W)
    return x


@dataclass(frozen=True)
class MatmulPlan:
    """How a (K, N) matmul maps onto computing blocks."""
    K: int
    N: int
    rows: int
    tiles_per_block: int          # D: tiles accumulated in analog
    outs_per_block: int           # outputs sharing a block
    n_tiles: int                  # total row tiles (ceil(K / rows))
    n_block_groups: int           # ceil(n_tiles / D): digital partial sums


def plan_matmul(K: int, N: int, acfg: AnalogConfig,
                geom: BlockGeometry) -> MatmulPlan:
    n_tiles = -(-K // acfg.rows)
    d = geom.tiles
    return MatmulPlan(K=K, N=N, rows=acfg.rows, tiles_per_block=d,
                      outs_per_block=geom.outputs, n_tiles=n_tiles,
                      n_block_groups=-(-n_tiles // d))


@dataclass(frozen=True)
class ConductancePlan:
    """Precomputed block layout of one (K, N) weight matrix.

    Conductance features are batch-constant: tiling, padding and the
    per-block (G+, G-) interleave run ONCE when a weight tag is bound, not
    on every forward call.  `g_feat` is indexed by block (NB * NO blocks)
    and broadcast over the batch lazily by whichever backend consumes it.

    `out_perm` (optional) records a fault-aware remapping of logical
    output columns onto physical block positions: `g_feat`'s NO axis holds
    the *permuted* layout and `assemble` gathers outputs back into logical
    order with `y[:, out_perm]`.  Remapping acts at output-group
    granularity (whole blocks move; a block is the atomic unit every
    backend evaluates, so moving one is bit-exact at the ideal point --
    conv/FC feature mixing happens only *within* a block).  `out_perm` may
    be a traced argument: permutation swaps never recompile consumers.
    """
    K: int
    N: int
    rows: int                     # H: wordlines per tile
    D: int                        # tiles accumulated in analog per block
    NB: int                       # block groups over K (digital partial sums)
    NO: int                       # output groups over N
    no: int                       # outputs per block
    g_feat: jax.Array             # (NB, NO, D, H, W=2*no) raw conductances [S]
    g_range: Tuple[float, float]  # (g_min, g_max) of the normalization
    out_perm: Optional[jax.Array] = None   # (N,) logical col -> physical col

    @property
    def g_norm(self) -> jax.Array:
        """``g_feat`` normalized to [0, 1] for the emulator.  Derived on
        use, not stored: a full-width plan cache would otherwise hold
        every site's conductances twice on the device."""
        g_min, g_max = self.g_range
        return (self.g_feat - g_min) / (g_max - g_min)

    @property
    def n_blocks(self) -> int:
        return self.NB * self.NO

    def with_perm(self, out_perm: Optional[jax.Array]) -> "ConductancePlan":
        """Same layout and conductances, different output gather.  The
        caller is responsible for `g_feat` already holding the matching
        permuted group layout (see `nonideal.perturb.remap_plan`)."""
        return dataclasses.replace(self, out_perm=out_perm)

    def with_lattice(self, g_feat: jax.Array, acfg: AnalogConfig, *,
                     NB: Optional[int] = None,
                     NO: Optional[int] = None) -> "ConductancePlan":
        """A LOCAL view of this plan over a slice of the tile lattice:
        same geometry (rows/D/no), a reduced block-group (NB) and/or
        output-group (NO) count, and the matching ``g_feat`` slice.
        ``repro.parallel.sharding`` builds one per shard inside the
        executor's ``shard_map`` body -- every backend evaluates blocks
        independently, so computing on a lattice slice is bit-identical
        to slicing the full computation.  The output permutation is
        dropped: the fault-remap gather runs on the full post-psum
        output, never on a shard-local slice."""
        return dataclasses.replace(
            self, NB=self.NB if NB is None else NB,
            NO=self.NO if NO is None else NO,
            g_feat=g_feat, g_range=(acfg.g_min, acfg.g_max), out_perm=None)

    def with_g(self, g_feat: jax.Array, acfg: AnalogConfig) -> "ConductancePlan":
        """Same block layout, different conductances (repro.nonideal injects
        perturbed devices here).  g_norm is rederived so every consumer --
        circuit, analytic, emulator fast path, Pallas kernel -- sees the
        perturbation.  Static fields are unchanged, so compiled functions
        built for this plan's shapes are reused when g_feat is a traced
        argument."""
        return dataclasses.replace(self, g_feat=g_feat,
                                   g_range=(acfg.g_min, acfg.g_max))

    def tile_v(self, v01: jax.Array, v_read: float) -> jax.Array:
        """(M, K) wordline drive in [0,1] -> (M, NB, D, H) tile voltages."""
        M = v01.shape[0]
        v = pad_rows(v01, self.rows, axis=1)
        T = v.shape[1] // self.rows
        vt = v.reshape(M, T, self.rows) * v_read
        padT = self.NB * self.D - T
        if padT:
            vt = jnp.pad(vt, ((0, 0), (0, padT), (0, 0)))
        return vt.reshape(M, self.NB, self.D, self.rows)

    def build_x(self, vb: jax.Array) -> jax.Array:
        """vb: (M, NB, D, H) volts -> (M*NB*NO, 2, D, H, W) raw block-feature
        tensors (the layout circuit/analytic backends consume)."""
        M = vb.shape[0]
        shp = (M, self.NB, self.NO, self.D, self.rows, 2 * self.no)
        v = jnp.broadcast_to(vb[:, :, None, :, :, None], shp)
        g = jnp.broadcast_to(self.g_feat[None], shp)
        x = jnp.stack([v, g], axis=3)         # (M, NB, NO, 2, D, H, W)
        return x.reshape(M * self.n_blocks, 2, self.D, self.rows, 2 * self.no)

    def assemble(self, outs: jax.Array) -> jax.Array:
        """(M*NB*NO, no) block outputs -> (M, N) digital block-group sum.
        With `out_perm` set, physical block outputs are gathered back into
        logical column order (the inverse of the fault-aware remap)."""
        M = outs.shape[0] // self.n_blocks
        if self.out_perm is None:
            y = outs.reshape(M, self.NB, self.NO * self.no)[:, :, :self.N]
            return y.sum(axis=1)
        y = outs.reshape(M, self.NB, self.NO * self.no).sum(axis=1)
        return jnp.take(y, self.out_perm, axis=1)


def build_conductance_plan(w: jax.Array, acfg: AnalogConfig,
                           geom: BlockGeometry) -> ConductancePlan:
    """Tile + pad + interleave a (K, N) weight matrix once."""
    K, N = w.shape
    gp, gn = tile_matrix(w, acfg)                     # (T, H, N)
    T = gp.shape[0]
    D = geom.tiles
    padT = (-T) % D
    if padT:
        gp = jnp.pad(gp, ((0, padT), (0, 0), (0, 0)))
        gn = jnp.pad(gn, ((0, padT), (0, 0), (0, 0)))
    NB = (T + padT) // D
    no = geom.outputs
    padN = (-N) % no
    if padN:
        gp = jnp.pad(gp, ((0, 0), (0, 0), (0, padN)))
        gn = jnp.pad(gn, ((0, 0), (0, 0), (0, padN)))
    NO = (N + padN) // no
    H = acfg.rows
    gpb = gp.reshape(NB, D, H, NO, no)
    gnb = gn.reshape(NB, D, H, NO, no)
    g = jnp.stack([gpb, gnb], axis=-1).reshape(NB, D, H, NO, 2 * no)
    g_feat = g.transpose(0, 3, 1, 2, 4)               # (NB, NO, D, H, W)
    return ConductancePlan(K=K, N=N, rows=H, D=D, NB=NB, NO=NO, no=no,
                           g_feat=g_feat, g_range=(acfg.g_min, acfg.g_max))


# --------------------------------------------------------------------------- #
# Stuck-fault-aware remapping (classic fault-tolerant mapping)
# --------------------------------------------------------------------------- #
def _horizon_damage(g: np.ndarray, live: np.ndarray, fault: np.ndarray,
                    by_group, plan: ConductancePlan, acfg: AnalogConfig,
                    horizon: Sequence[np.ndarray]) -> np.ndarray:
    """Anticipated end-of-horizon damage matrix ``dmg[q, p]`` averaged
    over a drift trajectory.  Two terms per checkpoint:

      * **drifted stuck-off excess** -- at age t a live cell of logical
        group q placed at physical position p holds
        ``clip(g * df_p(t), g_min, g_max)`` (the decay multiplier
        belongs to the *physical* die position), and a stuck cell there
        reads g_min instead: the clipped, drifted overhang is what the
        fault costs once periodic recalibration has re-centered the
        fleet on its drifted response.
      * **drift mismatch** -- the healthy cells of a group hosted at a
        decay-outlier position deviate from the fleet-mean decay a
        global affine refit absorbs: ``g * |df_p - mean_p df|`` per live
        unfaulted cell.  Without this term a fast-drifting position
        looks deceptively "clean" (its fault excess decays away) and the
        assignment would park heavy groups on the die positions that
        decay them hardest.

    An all-ones trajectory zeroes the mismatch term and reduces the
    fault term to the instantaneous matrix exactly (live plan
    conductances already sit inside [g_min, g_max])."""
    gg = by_group(g)                                   # (NO, C) logical
    lv = by_group(live)
    C = gg.shape[1]
    cells_per_nb = C // plan.NB
    dmg = np.zeros((plan.NO, plan.NO))
    horizon = list(horizon)
    for df in horizon:
        d = np.asarray(df, np.float64)
        if d.ndim == 0:
            dfc = np.broadcast_to(d, (plan.NO, C))
        elif d.shape == (plan.NB, plan.NO):
            # per-tile decay -> per (physical group, cell) with the cell
            # axis (NB, D, H, W)-flattened NB-outermost, matching by_group
            dfc = np.repeat(d.T, cells_per_nb, axis=1)
        else:
            raise ValueError(
                f"horizon drift factor shaped {d.shape}; expected a "
                f"scalar or (NB, NO) = {(plan.NB, plan.NO)}")
        dbar = dfc.mean(axis=0)                        # fleet-mean decay
        for p in range(plan.NO):
            gd = np.clip(gg * dfc[p], acfg.g_min, acfg.g_max)
            ex = np.where(lv, (gd - acfg.g_min), 0.0)
            dmg[:, p] += ex @ fault[p]
            mis = np.where(lv, gg * np.abs(dfc[p] - dbar), 0.0)
            dmg[:, p] += mis @ (1.0 - fault[p])
    span = float(acfg.g_max - acfg.g_min)
    return dmg / (span * max(len(horizon), 1))


def _assignment_horizon_score(g: np.ndarray, off: np.ndarray,
                              gperm: np.ndarray, plan: ConductancePlan,
                              acfg: AnalogConfig,
                              horizon: Sequence[np.ndarray]) -> float:
    """Exact end-of-horizon weight-space deviation of an assignment.

    For each horizon drift factor, realize the effective cell
    conductances a device would serve with under the candidate
    permutation -- stuck-off cells pinned at ``g_min`` (the fault mask
    lives at *physical* positions), live cells decayed by the physical
    host's retention factor and clipped back into range -- fold the
    interleaved pos/neg pairs into differential weights, and measure
    ``min_a ||W_young - a * W_eff||_F^2`` over the real (un-padded)
    columns.  The scalar ``a`` is the global affine refit periodic
    recalibration performs, solved in closed form.  Averaged over the
    horizon; lower is better.  This is the model the greedy candidates
    are judged under, so the returned winner can never model-worse than
    instant remapping."""
    gperm = np.asarray(gperm)
    off_at = off[:, gperm]                             # fault mask seen by q
    live = g > 0.0
    # mask padded logical columns (dropped by the assemble gather)
    no = plan.no
    col = (np.arange(plan.NO)[:, None] * no + np.arange(no)[None, :])
    valid = (col < plan.N).astype(np.float64)          # (NO, no)
    vmask = valid[None, :, None, None, :]
    w_young = (g[..., 0::2] - g[..., 1::2]) * vmask
    total = 0.0
    horizon = list(horizon)
    for df in horizon:
        d = np.asarray(df, np.float64)
        if d.ndim == 0:
            dfq = np.broadcast_to(d, (plan.NB, plan.NO))
        elif d.shape == (plan.NB, plan.NO):
            dfq = d[:, gperm]                          # decay of q's host
        else:
            raise ValueError(
                f"horizon drift factor shaped {d.shape}; expected a "
                f"scalar or (NB, NO) = {(plan.NB, plan.NO)}")
        dfe = dfq[:, :, None, None, None]
        aged = np.clip(g * dfe, acfg.g_min, acfg.g_max)
        eff = np.where(off_at, acfg.g_min, np.where(live, aged, 0.0))
        w_eff = (eff[..., 0::2] - eff[..., 1::2]) * vmask
        ee = float((w_eff * w_eff).sum())
        a = float((w_eff * w_young).sum()) / ee if ee > 0.0 else 1.0
        r = w_young - a * w_eff
        total += float((r * r).sum())
    return total / max(len(horizon), 1)


def fault_aware_group_perm(g_feat: np.ndarray, stuck_off: np.ndarray,
                           plan: ConductancePlan, acfg: AnalogConfig,
                           top_q: float = 0.9,
                           horizon: Optional[Sequence[np.ndarray]] = None
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Permute logical output groups across physical block positions so
    large-magnitude weights avoid stuck-at-G_off cells.

    A cell stuck at G_off reads as weight zero: the damage it does equals
    the conductance excess `g - g_min` the plan wanted to program there.
    Remapping moves whole output groups (the blocks backends evaluate
    atomically, so the move is bit-exact at the ideal point; with
    `no == 1`, as in the paper's case-A geometry, that is per-column).
    The assignment is lexicographic: first minimize the number of
    top-`top_q`-quantile |w| cells landing on stuck-off sites, then the
    total excess landing there -- greedy over logical groups in descending
    order of top-weight mass, which pairs the most-vulnerable groups with
    the cleanest physical positions first (rearrangement-inequality
    heuristic).  Deterministic; the identity permutation falls out exactly
    when no stuck-off cell overlaps any programmed cell.

    Wear-aware mode (``horizon`` given): ``horizon`` is a sequence of
    retention-decay multipliers (``nonideal.perturb.drift_factor`` at the
    maintenance checkpoints), each a scalar or an (NB, NO) array indexed
    by *physical* tile.  A second candidate assignment is grown greedily
    under the anticipated-damage matrix (``_horizon_damage``: drifted
    stuck-off excess + drift-mismatch of healthy cells), and the instant
    and wear-aware candidates are then SCORED under the exact
    end-of-horizon weight-space deviation model
    (``_assignment_horizon_score``: realized differential weights under
    faults + per-position decay, with the global affine refit absorbed)
    -- the lower-scoring assignment wins, instant on ties.  Wear-aware
    remapping therefore never models-worse than instant remapping over
    the horizon, and genuinely wins when per-die drift heterogeneity
    makes slow-decaying positions the riskier hosts.  ``horizon=None``
    runs the instantaneous assignment, bit-identically to a call without
    the argument.

    Args:
      g_feat:    (NB, NO, D, H, W) base-plan conductances (logical layout).
      stuck_off: (NB, NO, D, H, W) boolean stuck-off mask at *physical*
                 positions (from `nonideal.perturb.realized_fault_masks`).
      plan:      the base plan (geometry only).
      acfg:      conductance range (g_min for the excess measure).
      top_q:     |w| quantile defining the protected cell set.
      horizon:   optional drift-factor trajectory for wear-aware scoring.

    Returns `(out_perm, gperm, ginv)` int arrays: `out_perm[j]` = physical
    column of logical column j (the `assemble` gather), `gperm[q]` =
    physical group of logical group q, `ginv[p]` = logical group at
    physical position p (the `g_feat` NO-axis gather).
    """
    g = np.asarray(g_feat, np.float64)
    off = np.asarray(stuck_off, bool)
    cands = _perm_candidates(g, off, plan, acfg, top_q, horizon)
    gperm = cands[0]
    if len(cands) > 1:
        s_inst = _assignment_horizon_score(g, off, cands[0], plan, acfg,
                                           horizon)
        s_wear = _assignment_horizon_score(g, off, cands[1], plan, acfg,
                                           horizon)
        if s_wear < s_inst:                            # instant wins ties
            gperm = cands[1]
    return finish_group_perm(gperm, plan)


def finish_group_perm(gperm: np.ndarray, plan: ConductancePlan
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Expand a logical->physical group assignment into the
    `(out_perm, gperm, ginv)` triple `fault_aware_group_perm` returns."""
    ginv = np.empty_like(gperm)
    ginv[gperm] = np.arange(plan.NO, dtype=np.int32)
    cols = np.arange(plan.N, dtype=np.int32)
    out_perm = gperm[cols // plan.no] * plan.no + cols % plan.no
    return out_perm.astype(np.int32), gperm, ginv


def _perm_candidates(g: np.ndarray, off: np.ndarray, plan: ConductancePlan,
                     acfg: AnalogConfig, top_q: float,
                     horizon: Optional[Sequence[np.ndarray]]) -> list:
    """Candidate group assignments: the instantaneous greedy first,
    plus -- when a ``horizon`` is given and it disagrees -- the
    wear-aware greedy grown under the anticipated-damage matrix.  The
    caller selects between them (model score here, realized score in
    ``nonideal.perturb.remap_plan``)."""
    span = float(acfg.g_max - acfg.g_min)
    live = g > 0.0
    # damage a stuck-off cell does = programmed excess over g_min, in
    # weight units; padded sites (no physical cell) carry none
    excess = np.where(live, (g - acfg.g_min) / span, 0.0)
    pos_excess = excess[excess > 0.0]
    if pos_excess.size == 0:
        return [np.arange(plan.NO, dtype=np.int32)]
    thr = np.quantile(pos_excess, top_q)
    top = (excess >= thr) & live                       # top-decile |w| cells
    # per-group flattening: (NB, NO, D, H, W) -> (NO, NB*D*H*W)
    by_group = lambda a: a.transpose(1, 0, 2, 3, 4).reshape(plan.NO, -1)
    fault = by_group(off)                              # physical positions
    excess_g = by_group(excess)                        # logical groups
    top_g = by_group(top).astype(np.float64)
    hits = np.einsum("pc,qc->qp", fault, top_g)
    # greedy: most-vulnerable logical groups pick first -- ordered by
    # top-decile cell count FIRST (its own scale: a group's total excess
    # routinely exceeds dmg.max(), which is damped by the sparse mask)
    vbig = excess_g.sum(axis=1).max() + 1.0
    vuln = top_g.sum(axis=1) * vbig + excess_g.sum(axis=1)
    order = np.argsort(-vuln, kind="stable")

    def greedy(dmg: np.ndarray) -> np.ndarray:
        big = dmg.max() + 1.0
        cost = hits * big + dmg                        # lexicographic
        gp = np.full(plan.NO, -1, dtype=np.int32)
        free = np.ones(plan.NO, bool)
        for q in order:
            c = np.where(free, cost[q], np.inf)
            best = c.min()
            # prefer staying home on ties -> identity when fault-free
            p = int(q) if (free[q] and c[q] <= best) else int(np.argmin(c))
            gp[q] = p
            free[p] = False
        return gp

    cands = [greedy(np.einsum("pc,qc->qp", fault, excess_g))]
    if horizon is not None:
        cand = greedy(_horizon_damage(g, live, fault, by_group, plan, acfg,
                                      horizon))
        if not np.array_equal(cand, cands[0]):
            cands.append(cand)
    return cands
