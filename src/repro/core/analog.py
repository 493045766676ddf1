"""AnalogMatmul: execute dense projections on emulated crossbar hardware.

Backends (config ``analog.backend``):
  digital   -- plain matmul (technique off; baseline)
  analytic  -- expert analytical model (paper's strawman)
  circuit   -- Newton-Raphson circuit solver (exact, slow; SPICE stand-in)
  emulator  -- trained Conv4Xbar regression net (the paper's contribution)

Execution model (see core/crossbar.py): weights are tiled onto differential
1T1R crossbars; activations drive wordlines dual-rail (v+ = relu(x),
v- = relu(-x)); blocks of D tiles accumulate in analog, block groups sum
digitally; a per-layer affine calibration maps block output voltages back to
logical units. The backward pass is the straight-through digital gradient
(hardware-aware training), via custom_vjp.

Serving fast path (docs/performance.md): the conductance plan for a weight
tag (tiling, padding, block interleave) is cached and reused across calls;
both voltage rails are evaluated in ONE blockified pass -- the emulator
backend reconstructs them from a single magnitude-drive CELU against the
precomputed zero-voltage block response, other backends stack the rails on
the batch axis.  The emulator evaluation goes through ONE dispatcher
(``kernels.emulator_block.emulator_block_unified``): a single fused Pallas
kernel on TPU (both rails, both GEMM stages, scenario epilogue -- one
compiled launch for every device corner) or the identical chunked XLA
schedule (``apply_blocklast``) elsewhere.

Deployment model (docs/api.md): everything that distinguishes a deployed
device from the ideal hardware -- perturbed conductances, read sigma and
key, the fault-remap output permutation, hot-swappable emulator params,
the scenario feature encoding a conditioned net consumes, and the
volts->logical calibration affine -- is bundled into ONE registered
pytree, ``core.deployment.DeploymentState``, threaded as ONE traced
argument through ONE jit cache per weight tag (``_unified_for``).
Swapping corners, ages, remap permutations, read cycles, calibrations or
retrained params therefore reuses a single compiled executable per
(tag, shape), and ``DeploymentState.ideal()`` reproduces the plain path
bit-identically (every non-ideal leaf sits at its exact-identity value).

Deployments are built with the immutable, fluent builder
``AnalogExecutor.deploy(scenario=..., age=..., remap=..., params=...,
key=...)`` -- the former mutable setter family (``set_scenario``,
``set_emulator_params``, assigning ``fault_remap``) survives as thin
deprecation shims for one release.  Non-ideality semantics
(docs/nonideal.md), fault-aware remapping and lifetime scheduling
(docs/lifetime.md) and the scenario-conditioned emulator
(docs/emulator.md) are unchanged; they now ride the unified forward.

Install into a model with ``use_dense_hook(executor.hook)`` -- every
``dense()`` in repro.models routes through here.  A ``ServeSession``
(``repro.launch.serve``) threads per-call-site ``DeploymentState``s
through its compiled serving steps, so task-level sweeps (accuracy vs
sigma / age on actual token prediction) swap device state with zero
recompiles.
"""
from __future__ import annotations

import contextlib
import functools
import warnings
import zlib
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AnalogConfig
from repro.configs.rram_ps32 import BlockGeometry, CASE_A
from repro.core import conv4xbar
from repro.core.analytic import analytic_block_response
from repro.core.circuit import CircuitParams, block_response
from repro.core.crossbar import ConductancePlan, build_conductance_plan
from repro.core.deployment import Deployment, DeploymentState
from repro.core.emulator import normalize_features
from repro.nonideal.perturb import (apply_read_noise, perturb_plan,
                                    remap_plan, scenario_circuit_params)
from repro.nonideal.scenario import (N_SCENARIO_FEATURES, Scenario,
                                     scenario_features,
                                     scenario_features_tiled)
from repro.obs import OBS
from repro.parallel.sharding import (DATA_AXIS, MODEL_AXIS, lattice_scheme,
                                     local_lattice, mesh_shape,
                                     shard_deployment_state, state_pspecs)

_UNSET = object()


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# --------------------------------------------------------------------------- #
# THE unified straight-through analog matmul.  One traced DeploymentState
# carries every deployed-device quantity (conductances, read sigma/key,
# remap permutation, emulator params, scenario features, calibration
# affine), so one executable per (tag, shape) serves the entire corner x
# age x remap x params manifold.  Hoisted to module level so the
# custom_vjp (and the per-tag jit wrapping it) is built once.
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _st_matmul_u(ex: "AnalogExecutor", tag: str, x2, w, st: DeploymentState):
    plan = ex._plan_for(w, tag).with_g(st.gf, ex.acfg).with_perm(st.out_perm)
    yv, xs = ex.raw_matmul(x2, w, tag, plan=plan, read_key=st.read_key,
                           read_sigma=st.read_sigma,
                           eparams=st.eparams if st.eparams else None,
                           sfeat=st.sfeat)
    return (st.cal_a * yv + st.cal_b) * xs


def _st_u_fwd(ex, tag, x2, w, st):
    return _st_matmul_u(ex, tag, x2, w, st), (x2, w, st)


def _zero_tangent(v):
    """Symbolic-zero cotangent for a state leaf (float0 for int leaves:
    the read key and the remap permutation are not differentiable)."""
    if jnp.issubdtype(jnp.result_type(v), jnp.floating):
        return jnp.zeros_like(v)
    return np.zeros(jnp.shape(v), jax.dtypes.float0)


def _st_u_bwd(ex, tag, res, ct):
    x2, w, st = res                    # straight-through digital grads;
    # nothing in the deployment state is a trained quantity (cotangent
    # dtypes must match the primals: w may be served in bf16)
    return ((ct @ w.T).astype(x2.dtype), (x2.T @ ct).astype(w.dtype),
            jax.tree.map(_zero_tangent, st))


_st_matmul_u.defvjp(_st_u_fwd, _st_u_bwd)


class _StateBinding:
    """Per-forward-pass resolution of dense() call sites to
    ``DeploymentState``s.

    Model tags repeat across layers (every block calls ``dense(...,
    "mlp.up")``), so a *site key* disambiguates by trace-order ordinal:
    the i-th call with tag T gets ``"T#i"``.  Trace order is
    deterministic, so site keys are stable across prefill / decode /
    processes.  In record mode the binding collects ``site_key ->
    weight`` (under ``jax.eval_shape``: zero FLOPs) for a ``ServeSession``
    to materialize states against; in serve mode it routes each site
    through the unified forward with that site's (typically traced)
    state.

    Scanned models (``lax.scan`` over layer periods) thread their states
    as scan xs: the binding doubles as the model's scan-states provider
    (``models.common.use_scan_states``).  Sites inside scan group ``g``,
    period ``p`` are keyed ``"{g}.{p}:{tag}#{j}"`` with the ordinal ``j``
    counted within the period (``scan_record``); at serve time
    ``scan_pick`` selects the current period's states by the traced
    period index the scan carries (``scan_slice``), and ``intercept``
    resolves sites from that selection --
    the traced weight slice takes the executor's eager in-trace path, so
    the whole scan stays inside ONE compiled serving step."""

    def __init__(self, states: Optional[Dict[str, DeploymentState]] = None,
                 record: Optional[Dict[str, jax.Array]] = None):
        self.states = states
        self.record = record
        self._ordinals: Dict[str, int] = {}
        self._prefix = ""
        self._slice: Optional[Dict[str, DeploymentState]] = None

    @property
    def recording(self) -> bool:
        return self.record is not None

    def site_key(self, tag: str) -> str:
        i = self._ordinals.get(tag, 0)
        self._ordinals[tag] = i + 1
        return f"{self._prefix}{tag}#{i}"

    @contextlib.contextmanager
    def _scoped(self, prefix: str, slice_states):
        """Fresh within-period ordinals + key prefix / slice lookup for
        the duration (scan bodies re-enter per period / per trace, so the
        reset also makes remat's double-trace idempotent)."""
        saved = (self._ordinals, self._prefix, self._slice)
        self._ordinals, self._prefix, self._slice = {}, prefix, slice_states
        try:
            yield
        finally:
            self._ordinals, self._prefix, self._slice = saved

    def scan_record(self, group: str, period: int):
        """Record mode: key the sites of one Python-unrolled period."""
        return self._scoped(f"{group}.{period}:", None)

    def scan_slice(self, group: str, ls):
        """Serve mode: resolve the scan body's sites from the traced
        per-period state slice ``ls`` (keyed by within-period site key)."""
        return self._scoped(f"{group}.?:", ls)

    def scan_pick(self, group: str, n: int):
        """``pick(i)``: the bound states of period ``i`` of scan group
        ``group`` as ``{inner_site_key: DeploymentState}``, selected by a
        ``lax.switch`` on the traced period index -- only the current
        period's states are copied, never a stack of all of them.
        Returns None when the group has no bound states (digital scan
        layers)."""
        if self.states is None:
            return None
        pre = f"{group}."
        per: list = [dict() for _ in range(n)]
        for sk, st in self.states.items():
            if not sk.startswith(pre) or ":" not in sk:
                continue
            p_str, inner = sk[len(pre):].split(":", 1)
            per[int(p_str)][inner] = st
        if not per[0]:
            return None
        keys = sorted(per[0])
        if any(sorted(d) != keys for d in per):
            raise KeyError(
                f"scan group {group!r}: per-period site keys differ "
                f"across the {n} periods (bound: {sorted(self.states)}); "
                "a saved deployment must be served with the model / "
                "layer configuration it was saved from")
        branches = [lambda d=d: {k: d[k] for k in keys} for d in per]
        return lambda i: jax.lax.switch(i, branches)

    def intercept(self, ex: "AnalogExecutor", x, w, tag: str):
        sk = self.site_key(tag)
        if self.record is not None:
            self.record[sk] = w
            return None                # digital fallback while recording
        if self._slice is not None:
            # inside a scan body: the key's period field is positional
            # (the xs slice IS period p); look up by within-period key
            st = self._slice.get(sk.split(":", 1)[1])
        else:
            st = self.states.get(sk) if self.states is not None else None
        if st is None:
            # a silent digital fallback here would break the round-trip
            # contract without a trace -- fail loudly instead
            bound = sorted(self._slice) if self._slice is not None \
                else sorted(self.states or ())
            raise KeyError(
                f"no DeploymentState bound for call site {sk!r} (bound: "
                f"{bound}); a saved deployment must be served with the "
                "model / layer configuration it was saved from")
        return ex.matmul(x, w, sk, state=st)


class AnalogExecutor:
    """Stateful serving executor for analog matmuls (see module docstring).

    Owns, per weight ``tag``: the cached conductance plan (``_plan_for``),
    ONE compiled unified forward (``_unified_for``) taking a single traced
    ``DeploymentState``, and the materialized-device-state cache
    (``_state_cache``).  The active ``Deployment`` (an immutable spec:
    scenario, fleet key, remap policy, hot-swapped params) is built with
    the fluent ``deploy(...)`` builder; per-tag states derive from it
    lazily via ``state_for``.  The legacy mutable setters delegate to
    ``deploy`` and emit ``DeprecationWarning``.
    """

    def __init__(self, acfg: AnalogConfig, geom: BlockGeometry = CASE_A,
                 cp: Optional[CircuitParams] = None,
                 emulator_params: Optional[dict] = None,
                 calibration: Optional[Dict[str, tuple]] = None,
                 fast_path: bool = True,
                 use_pallas: Optional[bool] = None,
                 scenario: Optional[Scenario] = None,
                 scenario_key: Optional[jax.Array] = None,
                 fault_remap: bool = False,
                 mesh=None, shard_scheme: str = "auto"):
        self.acfg = acfg
        self.geom = geom
        self.cp = cp if cp is not None else CircuitParams()
        self._base_params = emulator_params
        self.calibration: Dict[str, tuple] = (
            calibration if calibration is not None else {})
        self.fast_path = fast_path            # cached-plan blockified path
        self.use_pallas = use_pallas          # None = auto (TPU only)
        # tensor-parallel serving (repro.parallel.sharding; docs/parallel.md):
        # a (data, model) mesh shards batch rows and the tile lattice; the
        # scheme ('auto' -> lattice_scheme, or forced 'row'/'col'/'none')
        # picks which lattice axis the model axis partitions
        self.mesh = mesh
        self.shard_scheme = shard_scheme

        self._plans: Dict[str, Tuple[jax.Array, ConductancePlan]] = {}
        # ONE jit-cache family: tag -> (w, r_line_scale, fn(x2, state))
        self._fns: Dict[str, Tuple[jax.Array, float, Callable]] = {}
        self._g0_cache: Dict[str, Tuple[ConductancePlan, dict]] = {}
        self._aux = None
        self._aux_src = None
        # tag -> (plan, deployment, base_state, perturbed_plan)
        self._state_cache: Dict[str, tuple] = {}
        self._binding: Optional[_StateBinding] = None
        self._read_calls = 0
        self._last_calib_n = 0
        # scenario-feature cache (one encode per Scenario object) and the
        # zero vector the ideal state carries -- one stable
        # (N_SCENARIO_FEATURES,) aval either way
        self._sfeat_ent: Optional[tuple] = None
        self._zero_sfeat = jnp.zeros((N_SCENARIO_FEATURES,), jnp.float32)

        if scenario is None and self.acfg.scenario:
            from repro.nonideal import get_scenario
            scenario = get_scenario(self.acfg.scenario)
        self._deployment = Deployment(
            scenario=scenario,
            key=(scenario_key if scenario_key is not None
                 else jax.random.PRNGKey(0)),
            remap=fault_remap)

    # ------------------------------------------------------------------ #
    # The immutable deployment (repro.core.deployment)
    # ------------------------------------------------------------------ #
    @property
    def deployment(self) -> Deployment:
        """The active immutable deployment spec."""
        return self._deployment

    @property
    def scenario(self) -> Optional[Scenario]:
        """The active deployment's device corner (None = ideal)."""
        return self._deployment.scenario

    @property
    def scenario_key(self) -> jax.Array:
        """The active deployment's fleet fabrication key."""
        return self._deployment.key

    @property
    def fault_remap(self) -> bool:
        """Stuck-fault-aware remapping policy of the active deployment."""
        return self._deployment.remap

    @fault_remap.setter
    def fault_remap(self, value: bool):
        warnings.warn(
            "assigning AnalogExecutor.fault_remap is deprecated; use "
            "AnalogExecutor.deploy(remap=...)", DeprecationWarning,
            stacklevel=2)
        self.deploy(remap=bool(value))

    @property
    def emulator_params(self) -> Optional[dict]:
        """The serving emulator params: the deployment's hot-swapped
        override when set, else the params bound at construction."""
        return (self._deployment.params if self._deployment.params is not None
                else self._base_params)

    def deploy(self, *, scenario=_UNSET, age: Optional[float] = None,
               remap=_UNSET, params=_UNSET, key: Optional[jax.Array] = None,
               states=_UNSET) -> Deployment:
        """Activate (and return) a new immutable deployment.

        Fluent partial update: only the given fields change, everything
        else carries over from the active deployment.  ``scenario=None``
        clears the corner (ideal hardware); ``age`` rewrites the
        scenario's ``drift_t`` (seconds since programming; the fleet ages,
        it is not refabricated); ``remap`` sets the stuck-fault-aware
        remapping policy (``True`` = instantaneous; a sequence of
        checkpoint ages in seconds = wear-aware horizon scoring,
        ``nonideal.remap_plan``); ``params`` hot-swaps retrained emulator
        params;
        ``key`` refabricates the fleet (a fixed key across deploys models
        the SAME devices under different conditions); ``states`` installs
        preloaded per-tag states (``core.deployment.load_deployment``).

        Invalidates only the materialized device-state cache and the
        read-cycle counter.  Nothing compiled is touched: every leaf of a
        ``DeploymentState`` is a traced argument of the unified forward,
        so a corner -> age -> remap -> params swap sequence reuses one
        executable per (tag, shape).
        """
        dep = self._deployment
        sc = dep.scenario if scenario is _UNSET else scenario
        if age is not None:
            if sc is None:
                raise ValueError("deploy(age=...) needs a scenario to age")
            from repro.nonideal.lifetime import scenario_at_age
            sc = scenario_at_age(sc, age)
        if remap is not _UNSET and isinstance(remap, (tuple, list)):
            # wear-aware remapping: a horizon of checkpoint ages (seconds)
            remap = tuple(float(t) for t in remap)
        new = Deployment(
            scenario=sc,
            key=dep.key if key is None else key,
            remap=(dep.remap if remap is _UNSET
                   else remap if isinstance(remap, tuple) else bool(remap)),
            params=dep.params if params is _UNSET else params,
            states=dep.states if states is _UNSET else states)
        self._deployment = new
        self._state_cache.clear()
        self._sfeat_ent = None
        self._read_calls = 0
        return new

    # ------------------------------------------------------------------ #
    # Deprecated mutable-setter shims (one release; docs/api.md)
    # ------------------------------------------------------------------ #
    def set_scenario(self, scenario: Optional[Scenario],
                     key: Optional[jax.Array] = None) -> "AnalogExecutor":
        """Deprecated: use ``deploy(scenario=..., key=...)``."""
        warnings.warn(
            "AnalogExecutor.set_scenario is deprecated; use "
            "AnalogExecutor.deploy(scenario=..., key=...)",
            DeprecationWarning, stacklevel=2)
        self.deploy(scenario=scenario, key=key)
        return self

    def set_emulator_params(self, params: dict) -> "AnalogExecutor":
        """Deprecated: use ``deploy(params=...)``."""
        warnings.warn(
            "AnalogExecutor.set_emulator_params is deprecated; use "
            "AnalogExecutor.deploy(params=...)",
            DeprecationWarning, stacklevel=2)
        self.deploy(params=params)
        return self

    # ------------------------------------------------------------------ #
    # Device-state materialization
    # ------------------------------------------------------------------ #
    @property
    def emulator_conditioned(self) -> bool:
        """True when the bound emulator params are scenario-conditioned
        (peripheral width > 2: fc0 has rows for ``scenario_features``).
        Static -- derived from param shapes -- so callers may branch on it
        at trace time (docs/emulator.md)."""
        return (self.emulator_params is not None
                and conv4xbar.n_periph_of(self.emulator_params,
                                          self.geom) > 2)

    def _scenario_features(self) -> jax.Array:
        """Feature encoding of the active scenario, cached per Scenario
        object (the encode is a handful of scalar reductions, but matmul
        is the serving hot path).  A tile-indexed scenario encodes as the
        per-tile ``(NB, NO, F)`` feature lattice
        (``scenario_features_tiled``), so a conditioned net sees each
        tile's own corner rather than fleet mean/max summaries; scalar
        corners keep the flat ``(F,)`` vector (one extra executable per
        tag when a deployment switches between the two shapes).  Forced
        eager: the deployment's scenario leaves are concrete state, and
        under an ENCLOSING jit (serve loop) the encode must come out
        concrete so the cache never holds a leaked tracer."""
        sc = self.scenario
        ent = self._sfeat_ent
        if ent is not None and ent[0] is sc:
            return ent[1]
        with jax.ensure_compile_time_eval():
            v = (scenario_features_tiled(sc)
                 if sc.tile_shape is not None else scenario_features(sc))
        self._sfeat_ent = (sc, v)
        return v

    def _tag_key(self, tag: str) -> jax.Array:
        """Per-tag device-draw key; crc32 keeps it stable across processes
        (hash() is salted per interpreter run)."""
        return jax.random.fold_in(self.scenario_key,
                                  zlib.crc32(tag.encode()) & 0x7FFFFFFF)

    def _next_read_key(self) -> jax.Array:
        """Fresh key per read cycle; the sequence restarts at deploy()
        so a serve run with a fixed --seed is reproducible end to end."""
        k = jax.random.fold_in(
            jax.random.fold_in(self.scenario_key, 0x5245AD), self._read_calls)
        self._read_calls += 1
        return k

    def _base_state(self, tag: str, w: jax.Array) -> DeploymentState:
        """The deployment's device state for ``(tag, w)``: the scenario's
        perturbation (and, under ``remap``, the stuck-fault-aware
        permutation) materialized once per (tag, plan, deployment) and
        cached -- with unit affine and a placeholder read key
        (``state_for`` stamps the serving-time ones)."""
        dep = self._deployment
        plan = self._plan_for(w, tag)
        ent = self._state_cache.get(tag) if tag else None
        if ent is not None and ent[0] is plan and ent[1] is dep:
            if OBS.enabled:
                OBS.counter("analog_state_cache_total",
                            "materialized device-state cache lookups",
                            tag=tag, event="hit").inc()
            return ent[2]
        if OBS.enabled:
            OBS.counter("analog_state_cache_total",
                        "materialized device-state cache lookups",
                        tag=tag or "<anon>", event="miss").inc()
        sc = dep.scenario
        with OBS.span("analog_state_build", tag=tag or "<anon>"), \
                jax.ensure_compile_time_eval():
            ep = (self.emulator_params
                  if self.acfg.backend == "emulator"
                  and self.emulator_params is not None else {})
            if sc is None or sc.is_ideal:
                pplan = plan.with_perm(jnp.arange(plan.N, dtype=jnp.int32))
                rsig = jnp.zeros((plan.NB, plan.NO), jnp.float32)
                sfeat = self._zero_sfeat
            else:
                key = self._tag_key(tag)
                base, operm = plan, jnp.arange(plan.N, dtype=jnp.int32)
                if dep.remap and sc.has_stuck_off:
                    # a tuple remap policy is a wear-aware horizon of
                    # checkpoint ages; True = instantaneous remapping
                    hz = dep.remap if isinstance(dep.remap, tuple) else None
                    base, operm = remap_plan(plan, self.acfg, sc, key,
                                             horizon=hz)
                pplan = perturb_plan(base, self.acfg, sc,
                                     key).with_perm(operm)
                # read sigma always enters tile-shaped so scalar and
                # per-tile scenarios share ONE compiled forward per tag
                rsig = jnp.broadcast_to(
                    jnp.asarray(sc.read_sigma, jnp.float32),
                    (plan.NB, plan.NO))
                sfeat = (self._scenario_features()
                         if self.acfg.backend == "emulator"
                         and self.emulator_conditioned else self._zero_sfeat)
            st = DeploymentState(
                # f32 regardless of the weights' dtype: one stable aval
                # for the ideal AND every perturbed corner
                gf=pplan.g_feat.astype(jnp.float32), read_sigma=rsig,
                read_key=jax.random.PRNGKey(0), out_perm=pplan.out_perm,
                eparams=ep, sfeat=sfeat,
                cal_a=jnp.asarray(1.0, jnp.float32),
                cal_b=jnp.asarray(0.0, jnp.float32))
        if tag:
            self._state_cache[tag] = (plan, dep, st, pplan)
        return st

    def state_for(self, tag: str, w: jax.Array) -> DeploymentState:
        """The ready-to-serve ``DeploymentState`` for ``(tag, w)``: the
        cached device state stamped with the current calibration affine
        and, when the corner draws read noise, a fresh read-cycle key.
        Preloaded states (``deploy(states=...)``) are served verbatim --
        they carry their saved affine and read key."""
        dep = self._deployment
        if dep.states is not None and tag in dep.states:
            # preloaded states still get mesh placement: this is the
            # re-shard-on-load path for deployments saved under a
            # different (or no) mesh shape (docs/parallel.md)
            return self.shard_state(dep.states[tag])
        st = self._base_state(tag, w)
        a, b = self.calibration.get(tag, (1.0, 0.0))
        st = st.with_calibration(a, b)
        sc = dep.scenario
        if sc is not None and sc.has_read_noise:
            st = st.with_read_key(self._next_read_key())
        return self.shard_state(st)

    def _inline_state(self, tag: str, w: jax.Array, a, b) -> DeploymentState:
        """State for the in-trace path (enclosing jit / grad / anonymous
        tag).  With a bound weight the cached state is reused (its
        concrete leaves bake into the enclosing executable, exactly as
        the pre-unification trace-time path did); under traced weights
        (hardware-aware training) the state derives in-trace."""
        dep = self._deployment
        if dep.states is not None and tag in dep.states:
            return dep.states[tag]
        if tag and not _is_tracer(w):
            return self.state_for(tag, w)
        plan = self._plan_for(w, tag)
        ep = (self.emulator_params
              if self.acfg.backend == "emulator"
              and self.emulator_params is not None else {})
        st = DeploymentState.ideal(plan, eparams=ep, calibration=(a, b))
        sc = dep.scenario
        if sc is not None and not sc.is_ideal:
            pplan = perturb_plan(plan, self.acfg, sc, self._tag_key(tag))
            kw = dict(gf=pplan.g_feat,
                      read_sigma=jnp.broadcast_to(
                          jnp.asarray(sc.read_sigma, jnp.float32),
                          (plan.NB, plan.NO)))
            if sc.has_read_noise:
                kw["read_key"] = self._next_read_key()
            if self.acfg.backend == "emulator" and self.emulator_conditioned:
                kw["sfeat"] = self._scenario_features()
            st = st.replace(**kw)
        return st

    def _scenario_plan(self, tag: str, w: jax.Array) -> ConductancePlan:
        """Device-state perturbed (and, with ``remap``, stuck-fault
        remapped) conductance plan -- the plan-shaped view of
        ``_base_state``, stable per (tag, plan, deployment) so
        identity-keyed caches (``_pre_for``) hit across eager calls."""
        self._base_state(tag, w)
        return self._state_cache[tag][3]

    def _cp_effective(self) -> CircuitParams:
        """CircuitParams with the scenario's line-resistance scaling (static:
        only the circuit backend reads it, and changing it recompiles)."""
        if self.scenario is not None:
            return scenario_circuit_params(self.cp, self.scenario)
        return self.cp

    # ------------------------------------------------------------------ #
    # Conductance-plan cache
    # ------------------------------------------------------------------ #
    def _plan_for(self, w: jax.Array, tag: str) -> ConductancePlan:
        """Tile/pad/interleave once per bound weight; rebuilt only when the
        tag is rebound to a different array (or under tracing)."""
        if _is_tracer(w):
            return build_conductance_plan(w, self.acfg, self.geom)
        ent = self._plans.get(tag) if tag else None
        if ent is not None and ent[0] is w:
            if OBS.enabled:
                OBS.counter("analog_plan_cache_total",
                            "conductance-plan cache lookups per weight tag",
                            tag=tag, event="hit").inc()
            return ent[1]
        if OBS.enabled:
            OBS.counter("analog_plan_cache_total",
                        "conductance-plan cache lookups per weight tag",
                        tag=tag or "<anon>", event="miss").inc()
        # force eager evaluation even under an enclosing jit trace: the plan
        # must come out concrete so it is computed once and cached, not
        # re-tiled inside the compiled graph on every call
        with OBS.span("analog_plan_build", tag=tag or "<anon>"), \
                jax.ensure_compile_time_eval():
            plan = build_conductance_plan(w, self.acfg, self.geom)
            if self.mesh is not None:
                # sharded like the states made from it: at full width the
                # plans of every site do not fit on one device
                from jax.sharding import NamedSharding
                spec = state_pspecs(self._scheme_for(plan.NB, plan.NO))["gf"]
                plan = plan.with_g(jax.device_put(
                    plan.g_feat, NamedSharding(self.mesh, spec)), self.acfg)
        if tag:
            self._plans[tag] = (w, plan)
            self._g0_cache.pop(tag, None)
        return plan

    def _blocklast_aux(self, eparams: Optional[dict] = None) -> dict:
        """Stage-collapsed emulator weights (conv4xbar.blocklast_weights),
        cached per params binding.  ``eparams`` overrides the executor's
        own params (the unified forward passes the deployment state's
        traced params through here)."""
        params = self.emulator_params if eparams is None else eparams
        assert params is not None, \
            "emulator backend needs trained params (core.emulator)"
        if any(_is_tracer(v) for v in params.values()):
            return conv4xbar.blocklast_weights(params, self.geom)
        if self._aux is None or self._aux_src is not params:
            with jax.ensure_compile_time_eval():
                self._aux = conv4xbar.blocklast_weights(params, self.geom)
            self._aux_src = params
            self._g0_cache.clear()
        return self._aux

    def _pre_for(self, plan: ConductancePlan, tag: str, aux: dict) -> dict:
        """Batch-independent tensors of the XLA fast path (zero-voltage
        block response and its stage-1 projection), cached per (tag,
        plan).  The Pallas kernel derives them in VMEM instead."""
        if _is_tracer(plan.g_feat) or any(_is_tracer(v) for v in aux.values()
                                          if isinstance(v, jax.Array)):
            return conv4xbar.blocklast_precompute(aux, plan.g_norm)
        ent = self._g0_cache.get(tag) if tag else None
        if ent is not None and ent[0] is plan:
            return ent[1]
        with jax.ensure_compile_time_eval():
            pre = conv4xbar.blocklast_precompute(aux, plan.g_norm)
        if tag:
            self._g0_cache[tag] = (plan, pre)
        return pre

    # ------------------------------------------------------------------ #
    # Backends
    # ------------------------------------------------------------------ #
    def _backend_fn(self, eparams: Optional[dict] = None):
        """Block-response function of the configured backend; ``eparams``
        overrides the executor's emulator params (hot-swap path)."""
        b = self.acfg.backend
        cp = self._cp_effective()
        if b == "circuit":
            return lambda x, p: block_response(x, cp, p)
        if b == "analytic":
            return lambda x, p: analytic_block_response(x, cp, p)
        if b == "emulator":
            params = self.emulator_params if eparams is None else eparams
            assert params is not None, \
                "emulator backend needs trained params (core.emulator)"
            return lambda x, p: conv4xbar.apply_fused(
                params, normalize_features(x, self.acfg), p)
        raise ValueError(b)

    def block_outputs(self, x: jax.Array,
                      eparams: Optional[dict] = None,
                      sfeat: Optional[jax.Array] = None) -> jax.Array:
        """x: (NBLK, 2, D, H, W) raw-feature block tensors -> (NBLK, O).

        For a scenario-conditioned emulator the peripheral vector is
        widened to ``(gain, offset, *scenario_features)``; ``sfeat=None``
        feeds the ideal corner's all-zero feature block.  A per-tile
        ``(NB, NO, F)`` sfeat is tiled across the batch rows -- the block
        rows are lattice-innermost (``ConductancePlan.build_x``), so each
        block gets its own tile's features."""
        n = x.shape[0]
        periph = jnp.concatenate(
            [jnp.ones((n, 1), x.dtype), jnp.zeros((n, 1), x.dtype)], axis=-1)
        if self.acfg.backend == "emulator":
            params = self.emulator_params if eparams is None else eparams
            npf = (conv4xbar.n_periph_of(params, self.geom)
                   if params is not None else 2)
            if npf > 2:
                if sfeat is None:
                    tail = jnp.zeros((n, npf - 2), x.dtype)
                elif sfeat.ndim >= 2:
                    t2 = sfeat.reshape(-1, sfeat.shape[-1]).astype(x.dtype)
                    tail = jnp.tile(t2, (n // t2.shape[0], 1))
                else:
                    tail = jnp.broadcast_to(sfeat.astype(x.dtype)[None],
                                            (n, npf - 2))
                periph = jnp.concatenate([periph, tail], axis=-1)
        return self._backend_fn(eparams)(x, periph)

    def _eval_blocks(self, plan: ConductancePlan, vb01: jax.Array,
                     eparams: Optional[dict] = None,
                     sfeat: Optional[jax.Array] = None) -> jax.Array:
        """vb01: (M, NB, D, H) wordline drive in [0, 1] -> (M*NB*NO, no).

        Only the slow paths route here (``fast_path=False`` or non-emulator
        backends); with the fast path on, the emulator backend goes through
        ``emulator_block_unified`` in ``raw_matmul`` -- on every device,
        Pallas or not."""
        x = plan.build_x(vb01 * self.acfg.v_read)
        return self.block_outputs(x.astype(jnp.float32), eparams, sfeat)

    def _drive01(self, u01: jax.Array) -> jax.Array:
        """Gate-overdrive wordline biasing (AnalogConfig.wl_overdrive): map
        nonzero normalized drives into [v_th/v_read, 1] so they clear the
        access transistor's cut-off instead of sitting in its deadband.
        Zero stays exactly zero -- the dual-rail delta factorization and
        padded tiles depend on it."""
        if not self.acfg.wl_overdrive:
            return u01
        t = self.cp.v_th / self.acfg.v_read
        return jnp.where(u01 > 0.0, t + u01 * (1.0 - t), 0.0)

    # ------------------------------------------------------------------ #
    # Tensor-parallel serving (docs/parallel.md)
    # ------------------------------------------------------------------ #
    def _scheme_for(self, nb: int, no: int) -> Optional[str]:
        """Lattice-sharding scheme for a (NB, NO) plan on this executor's
        mesh: 'auto' defers to ``lattice_scheme`` (col preferred -- it is
        bit-identical to the replicated path); a forced scheme is
        validated against the model-axis divisibility it requires."""
        _, tp = mesh_shape(self.mesh)
        if tp <= 1:
            return None
        if self.shard_scheme == "auto":
            return lattice_scheme(nb, no, tp)
        s = None if self.shard_scheme == "none" else self.shard_scheme
        if s not in (None, "row", "col"):
            raise ValueError(f"shard_scheme={self.shard_scheme!r} "
                             "(expected 'auto', 'row', 'col' or 'none')")
        if s == "col" and no % tp:
            raise ValueError(
                f"shard_scheme='col' needs NO % tp == 0 (NO={no}, tp={tp})")
        if s == "row" and nb % tp:
            raise ValueError(
                f"shard_scheme='row' needs NB % tp == 0 (NB={nb}, tp={tp})")
        return s

    def shard_state(self, st: DeploymentState) -> DeploymentState:
        """Place a ``DeploymentState``'s leaves on the serving mesh under
        the lattice partition specs (no-op without a mesh).  Idempotent,
        and re-shards states materialized elsewhere -- including host
        arrays npz-loaded from a deployment saved under a DIFFERENT mesh
        shape (``load_deployment(..., executor=...)``)."""
        if self.mesh is None:
            return st
        nb, no = int(st.gf.shape[0]), int(st.gf.shape[1])
        return shard_deployment_state(st, self.mesh,
                                      self._scheme_for(nb, no))

    def shard_states(self, states: Dict[str, DeploymentState]
                     ) -> Dict[str, DeploymentState]:
        """``shard_state`` over a per-site state dict (serve sessions,
        loaded deployments)."""
        return {k: self.shard_state(v) for k, v in states.items()}

    def _sharded_matmul(self, x2d: jax.Array, x_scale: jax.Array,
                        plan: ConductancePlan, tag: str,
                        eparams: Optional[dict],
                        sfeat: Optional[jax.Array]) -> jax.Array:
        """The dp x tp ``shard_map`` evaluation of one analog matmul.

        Everything order-sensitive stays OUTSIDE the shard_map exactly as
        the replicated path computes it -- the global drive scale, the
        wordline tiling, the read-noise draw on the FULL conductance field
        (so noise values are mesh-invariant), the scenario shift, and the
        fault-remap output gather (post-psum, on full columns).  Inside,
        each shard evaluates its lattice slice as a local
        ``ConductancePlan`` view (``with_lattice``) -- blocks are
        independent across the lattice, so the per-shard math is the
        replicated math restricted to a slice -- and ONE ``psum`` over
        the model axis completes the digital bitline accumulation:

          col: full per-column NB reduction locally, scatter into the
               owned column range, psum against exact zeros elsewhere
               (bit-identical to the replicated path);
          row: per-shard partial bitline sums, psum finishes the
               reduction (float-tolerance: the psum re-brackets the f32
               accumulation).

        Returns the calibrand voltages (B, N) with the output permutation
        (or padded-column slice) already applied."""
        from repro.parallel.collectives import shard_map_compat
        from jax.sharding import PartitionSpec as P

        mesh = self.mesh
        dp, tp = mesh_shape(mesh)
        scheme = self._scheme_for(plan.NB, plan.NO)
        nb_l, no_l = local_lattice(plan.NB, plan.NO, tp, scheme)
        gf_spec = state_pspecs(scheme)["gf"]
        no, NOno = plan.no, plan.NO * plan.no
        B = x2d.shape[0]

        fast = self.acfg.backend == "emulator" and self.fast_path
        if fast:
            aux = self._blocklast_aux(eparams)
            ep = self.emulator_params if eparams is None else eparams
            shift = (sfeat @ aux["f0_scen"]
                     if sfeat is not None and "f0_scen" in aux else None)
            u = plan.tile_v(self._drive01(jnp.abs(x2d) / x_scale), 1.0)
            pos = plan.tile_v((x2d > 0).astype(jnp.float32), 1.0)
            drives, R = (u, pos), B
        else:
            # the rails ride as SEPARATE operands, concatenated per-shard
            # inside the body: a batch-axis concat feeding a shard_map
            # operand is miscompiled by GSPMD on this jax version (each
            # row comes back multiplied by the model-axis size -- see
            # tests/test_multidevice.py), while ops inside the manual
            # region are plain local computations
            vp = plan.tile_v(self._drive01(jnp.clip(x2d, 0.0, None)
                                           / x_scale), 1.0)
            vn = plan.tile_v(self._drive01(jnp.clip(-x2d, 0.0, None)
                                           / x_scale), 1.0)
            ep, shift = eparams, None
            drives, R = (vp, vn), B

        # pad batch rows to a dp multiple with zero rows -- bit-neutral:
        # rows are independent and the drive scale is already fixed
        Rp = -(-R // dp) * dp
        if Rp != R:
            drives = tuple(
                jnp.pad(v, ((0, Rp - R),) + ((0, 0),) * (v.ndim - 1))
                for v in drives)
        # row scheme shards the drives' NB axis alongside gf; col/None
        # replicate them over model (columns share the wordline drive)
        d_spec = P(DATA_AXIS, MODEL_AXIS) if scheme == "row" \
            else P(DATA_AXIS)

        def _combine(y_cols, Ml):
            # y_cols: (Ml, no_l * no) -- this shard's full-NB column slice
            # (col) or all-column bitline partial (row / replicated)
            if scheme == "col":
                i = jax.lax.axis_index(MODEL_AXIS)
                y_cols = jax.lax.dynamic_update_slice(
                    jnp.zeros((Ml, NOno), y_cols.dtype), y_cols,
                    (0, i * no_l * no))
            if scheme is not None:
                y_cols = jax.lax.psum(y_cols, MODEL_AXIS)  # THE collective
            return y_cols

        # bodies take every traced quantity as an explicit arg (shard_map
        # rejects closed-over tracers) and rebuild the stage-collapsed
        # weights from the raw param arrays inside (aux carries static
        # kernel widths that cannot ride a PartitionSpec'd pytree)
        if fast:
            from repro.kernels.emulator_block import emulator_block_unified

            def body(u, pos, gf, ep, *sh):
                lp = plan.with_lattice(gf, self.acfg, NB=nb_l, NO=no_l)
                laux = conv4xbar.blocklast_weights(ep, self.geom)
                s = sh[0] if sh else None
                if s is not None and s.ndim == 3:
                    # per-tile shift: the spec sliced this shard's own
                    # (nb_l, no_l) lattice window; flatten to block order
                    s = s.reshape(-1, s.shape[-1])
                y2 = emulator_block_unified(
                    laux, lp.g_norm, u, pos, shift=s,
                    use_pallas=self.use_pallas)
                Ml = u.shape[0]
                asm = lambda o: o.reshape(Ml, nb_l, no_l * no).sum(axis=1)
                return _combine(asm(y2[0]) - asm(y2[1]), Ml)

            args = drives + (plan.g_feat, ep)
            in_specs = (d_spec, d_spec, gf_spec, P())
            if shift is not None:
                args += (shift,)
                # per-tile (NB, NO, fc0_out) shift rides the SAME lattice
                # axis as gf so each shard sees its own tiles' epilogue;
                # flat (fc0_out,) shifts replicate
                in_specs += ((gf_spec if shift.ndim == 3 else P()),)
        else:
            v_read = self.acfg.v_read

            def body(vp, vn, gf, ep, sf):
                lp = plan.with_lattice(gf, self.acfg, NB=nb_l, NO=no_l)
                # both rails in ONE blockified batch, as the replicated
                # path stacks them (local concat: safe inside the region)
                vb = jnp.concatenate([vp, vn], axis=0)
                x = lp.build_x(vb * v_read)
                outs = self.block_outputs(x.astype(jnp.float32), ep, sf)
                Ml = vp.shape[0]
                y = outs.reshape(2 * Ml, nb_l, no_l * no).sum(axis=1)
                return _combine(y[:Ml] - y[Ml:], Ml)

            args = drives + (plan.g_feat, ep, sfeat)
            # per-tile (NB, NO, F) features shard with the lattice (each
            # shard's block_outputs tiles its own window); flat vectors
            # and None replicate
            sf_spec = (gf_spec if sfeat is not None and sfeat.ndim == 3
                       else P())
            in_specs = (d_spec, d_spec, gf_spec, P(), sf_spec)

        y = shard_map_compat(body, mesh, in_specs, P(DATA_AXIS))(*args)
        if Rp != R:
            y = y[:R]
        # logical column order: the remap gather runs post-psum on the
        # full output, exactly as plan.assemble orders it
        return (jnp.take(y, plan.out_perm, axis=1)
                if plan.out_perm is not None else y[:, :plan.N])

    # ------------------------------------------------------------------ #
    def raw_matmul(self, x2d: jax.Array, w: jax.Array, tag: str = "",
                   plan: Optional[ConductancePlan] = None,
                   read_key: Optional[jax.Array] = None,
                   read_sigma=None,
                   eparams: Optional[dict] = None,
                   sfeat: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
        """Analog forward for (B,K) @ (K,N): dual-rail inputs, tiled blocks,
        digital block-group accumulation. Output in volts (uncalibrated).

        Both rails run as ONE blockified batch against the cached
        conductance plan for `tag`: the emulator fast path evaluates them
        via the shared-magnitude delta factorization (the unified
        kernel/dispatcher ``emulator_block_unified``), all other backends
        stack the rails on the batch axis.

        `plan` overrides the cached conductance plan (the unified forward
        passes the deployment state's device-perturbed, possibly
        fault-remapped plan); with `plan=None` and an active scenario the
        device-state perturbation is applied here, inside the trace.
        `read_key`/`read_sigma` add one cycle-to-cycle read-noise draw on
        top of whatever plan is in effect (`read_sigma` may be per-tile;
        sigma 0 is an exact bitwise identity).  `eparams` overrides the
        executor's emulator params (the deployment state's hot-swapped
        params arrive here as traced arguments).  `sfeat` is the
        scenario-feature vector a conditioned emulator consumes (all-zero
        = the ideal corner's encoding); with `sfeat=None` and an active
        scenario it is derived here, so the in-trace path conditions too."""
        if plan is None:
            plan = self._plan_for(w, tag)
            sc = self.scenario
            if sc is not None and not sc.is_ideal:
                if tag and not _is_tracer(plan.g_feat):
                    plan = self._scenario_plan(tag, w)   # cached device draw
                else:
                    plan = perturb_plan(plan, self.acfg, sc,
                                        self._tag_key(tag))
                if read_key is None and sc.has_read_noise:
                    read_key, read_sigma = self._next_read_key(), sc.read_sigma
                if sfeat is None and self.acfg.backend == "emulator" \
                        and eparams is None and self.emulator_conditioned:
                    sfeat = self._scenario_features()
        if read_key is not None:
            rs = 0.0 if read_sigma is None else read_sigma
            if self.mesh is not None and mesh_shape(self.mesh) != (1, 1):
                # The read-noise draw must be MESH-INVARIANT: jax's
                # default (non-partitionable) threefry changes values
                # when GSPMD partitions the counter computation, and
                # even with a pinned draw a partitioned elementwise
                # application leaves ulp-level fusion differences.  So
                # the whole noise block -- inputs, draw, output -- runs
                # replicated (P()) and the shard_map operand re-slices
                # the result; a deployment then serves the same noisy
                # conductances on every mesh shape, including none
                # (docs/parallel.md).
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                rep = NamedSharding(self.mesh, P())
                wsc = jax.lax.with_sharding_constraint
                gn = wsc(apply_read_noise(
                    wsc(plan.g_feat, rep), self.acfg,
                    wsc(jnp.asarray(rs, jnp.float32), rep), read_key), rep)
            else:
                gn = apply_read_noise(plan.g_feat, self.acfg, rs, read_key)
            plan = plan.with_g(gn, self.acfg)
        B = x2d.shape[0]
        x2d = x2d.astype(jnp.float32)
        x_scale = jnp.maximum(jnp.max(jnp.abs(x2d)), 1e-9)
        if self.mesh is not None and mesh_shape(self.mesh) != (1, 1):
            return self._sharded_matmul(x2d, x_scale, plan, tag,
                                        eparams, sfeat), x_scale
        if self.acfg.backend == "emulator" and self.fast_path:
            from repro.kernels.emulator_block.ops import (
                emulator_block_unified, runs_kernel)
            aux = self._blocklast_aux(eparams)
            pre = (None if runs_kernel(self.use_pallas)
                   else self._pre_for(plan, tag, aux))
            shift = None
            if sfeat is not None and "f0_scen" in aux:
                # conditioned corner contribution: a (fc0_out,) bias
                # shift, exactly zero at the ideal (all-zero) encoding;
                # per-tile (NB, NO, F) operands flatten to one
                # (NB*NO, fc0_out) shift per block in lattice order
                shift = sfeat @ aux["f0_scen"]
                if shift.ndim == 3:
                    shift = shift.reshape(-1, shift.shape[-1])
            u = plan.tile_v(self._drive01(jnp.abs(x2d) / x_scale), 1.0)
            pos = plan.tile_v((x2d > 0).astype(jnp.float32), 1.0)
            y2 = emulator_block_unified(aux, plan.g_norm, u, pos,
                                        shift=shift, pre=pre,
                                        use_pallas=self.use_pallas)
            return plan.assemble(y2[0]) - plan.assemble(y2[1]), x_scale
        rails = jnp.concatenate([jnp.clip(x2d, 0.0, None),
                                 jnp.clip(-x2d, 0.0, None)], axis=0)
        vb01 = plan.tile_v(self._drive01(rails / x_scale), 1.0)  # (2B,NB,D,H)
        outs = self._eval_blocks(plan, vb01.astype(jnp.float32), eparams,
                                 sfeat)
        y = plan.assemble(outs)                       # (2B, N)
        return y[:B] - y[B:], x_scale

    def calibrate(self, key, w: jax.Array, tag: str, n: int = 256,
                  noise_draws: int = 4, warm_start: bool = False):
        """Fit the per-layer affine volts->logical map against digital.

        Noise-aware: with an active scenario the fit runs against the same
        device state the serving path sees (the unified forward at unit
        affine), and the response is averaged over `noise_draws`
        cycle-to-cycle read draws so the affine targets the expected (not
        one-shot) transfer.  The fit reuses the tag's ONE compiled
        forward -- each read draw is just a new ``read_key`` leaf.

        ``warm_start=True`` transfers the previous affine instead of
        refitting from scratch (docs/lifetime.md "calibration transfer"):
        drift between checkpoints is mostly a scale shift, so the refit
        runs on HALF the probe budget with the previous ``(a, b)`` as a
        ridge prior.  Falls back to a cold full-budget fit when no
        previous affine exists.  The probe count actually used is
        recorded in ``_last_calib_n`` (asserted in tests)."""
        prev = self.calibration.get(tag) if warm_start else None
        n_eff = max(8, n // 2) if prev is not None else n
        xc = jax.random.normal(key, (n_eff, w.shape[0])) * 0.5
        sc = self.scenario
        st = self._base_state(tag, w)        # unit affine by construction
        draws = (max(1, noise_draws)
                 if sc is not None and sc.has_read_noise else 1)
        keys = jax.random.split(
            jax.random.fold_in(self.scenario_key, 0xCA11B), draws)
        fn = self._unified_for(tag, w)
        ys = jnp.stack([fn(xc, st.with_read_key(k))
                        for k in keys]).mean(axis=0)
        xs = jnp.maximum(jnp.max(jnp.abs(xc.astype(jnp.float32))), 1e-9)
        yv_flat = (ys / xs).reshape(-1)
        yd_flat = ((xc @ w) / xs).reshape(-1)
        A = jnp.stack([yv_flat, jnp.ones_like(yv_flat)], axis=1)
        rhs = yd_flat
        if prev is not None:
            # ridge prior toward the previous checkpoint's affine: one
            # synthetic row per parameter, each weighted at ~5% of the
            # data's leverage on THAT parameter (sum yv^2 for the scale,
            # the row count for the offset) so the probes still dominate
            la = jnp.sqrt(0.05 * jnp.sum(yv_flat * yv_flat) + 1e-12)
            lb = jnp.sqrt(0.05 * yv_flat.shape[0])
            A = jnp.concatenate(
                [A, jnp.asarray([[1.0, 0.0], [0.0, 1.0]], A.dtype)
                 * jnp.asarray([[la], [lb]], A.dtype)], axis=0)
            rhs = jnp.concatenate(
                [rhs, jnp.asarray([la * prev[0], lb * prev[1]], rhs.dtype)],
                axis=0)
        sol, *_ = jnp.linalg.lstsq(A, rhs)
        self.calibration[tag] = (float(sol[0]), float(sol[1]))
        self._last_calib_n = n_eff
        if OBS.enabled:
            # fleet health: RMS residual of the affine fit over the DATA
            # rows (prior rows excluded) -- a drifting device that the
            # affine can no longer linearize shows up here first.  All
            # arrays are concrete (this is an eager fit): recording them
            # cannot perturb anything served.
            res = yv_flat * sol[0] + sol[1] - yd_flat
            OBS.gauge("analog_calibration_residual",
                      "RMS residual of the volts->logical affine fit",
                      tag=tag).set(float(jnp.sqrt(jnp.mean(res * res))))
            OBS.gauge("analog_calibration_probes",
                      "probe budget used by the last calibration fit",
                      tag=tag).set(n_eff)
            OBS.counter("analog_calibrations_total",
                        "calibration fits per tag and start mode",
                        tag=tag,
                        mode="warm" if prev is not None else "cold").inc()
        return self.calibration[tag]

    # ------------------------------------------------------------------ #
    # THE per-tag compiled forward (the single surviving jit-cache family)
    # ------------------------------------------------------------------ #
    def _unified_for(self, tag: str, w: jax.Array) -> Callable:
        """Per-(tag, weight-binding) unified forward ``fn(x2, state)``.

        `w` is closed over as a concrete constant, so the cached
        conductance plan is computed at trace time; EVERYTHING deployed --
        conductances, read sigma/key, remap permutation, emulator params,
        scenario features, calibration affine -- arrives inside the one
        traced ``DeploymentState``, so corner / age / remap / read-cycle /
        recalibration / retrained-params swaps all reuse one executable
        per (tag, shape).  Only a line-resistance change rebuilds it
        (CircuitParams is a hashed static of the circuit backend).

        The read-noise draw and the output gather run even at sigma == 0 /
        identity permutations (exact identities there): a g_feat-sized
        threefry sample and an (N,)-gather are tens of microseconds
        against a millisecond-scale matmul, and keeping them unconditional
        preserves exactly ONE executable per tag."""
        ent = self._fns.get(tag)
        rls = self.scenario.r_line_scale if self.scenario else 1.0
        if ent is not None and ent[0] is w and ent[1] == rls:
            return ent[2]
        # close over the ORIGINAL weight binding: the plan's conductances
        # are replaced by the state's gf leaf anyway, and an f32 alias
        # would make the per-tag plan cache ping-pong between identities
        # for bf16-served weights
        if OBS.enabled:
            OBS.counter("analog_unified_builds_total",
                        "per-tag unified forwards (re)built -- each build "
                        "implies at least one fresh compile",
                        tag=tag).inc()

        def _fwd(x2, st):
            # trace-time side effect: counts compiles of THIS tag's
            # forward (pure Python -- the jaxpr is unchanged, so the
            # counter is compile- and bit-neutral by construction)
            if OBS.enabled:
                OBS.counter("analog_traces_total",
                            "jit traces of the per-tag unified forward",
                            tag=tag).inc()
            with jax.named_scope("analog:" + tag):
                return _st_matmul_u(self, tag, x2, w, st)

        fn = jax.jit(_fwd)
        self._fns[tag] = (w, rls, fn)
        return fn

    def matmul(self, x: jax.Array, w: jax.Array, tag: str = "",
               state: Optional[DeploymentState] = None) -> jax.Array:
        """Calibrated analog matmul with straight-through digital gradient.

        Compiles once per (tag, shape): the custom_vjp is module-level and
        the whole deployment -- device perturbation, remap, read cycle,
        emulator params, scenario features AND the calibration affine --
        enters as ONE traced ``DeploymentState``, so recalibration,
        scenario swaps, aging, remapping and retraining never retrigger
        compilation.  ``state`` overrides the active deployment's
        materialized state (``ServeSession`` threads per-call-site states
        through its compiled serving steps this way); by default the state
        derives from ``deploy(...)``'s spec, and the ideal deployment is
        bit-identical to the plain serving fast path."""
        # every device op of the site, the kernel included, carries
        # "analog:<site>" in its op_name (the device trace's per-site view)
        with jax.named_scope("analog:" + tag):
            lead = x.shape[:-1]
            x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
            if _is_tracer(x2) or _is_tracer(w) or not tag:
                mode = "eager"
                if state is None:
                    a, b = self.calibration.get(tag, (1.0, 0.0))
                    state = self._inline_state(tag, w, a, b)
                y = _st_matmul_u(self, tag, x2, w, state)
            else:
                mode = "jit"
                st = state if state is not None else self.state_for(tag, w)
                y = self._unified_for(tag, w)(x2, st)
            if OBS.enabled:
                # "jit" is the per-tag compiled forward; "eager" is the
                # in-trace / anonymous-tag path (under an enclosing jit it
                # counts once, at trace time)
                OBS.counter("analog_matmul_calls_total",
                            "analog matmul calls per tag and dispatch mode",
                            tag=tag or "<anon>", mode=mode).inc()
            return y.reshape(*lead, w.shape[1]).astype(x.dtype)

    # ------------------------------------------------------------------ #
    @contextlib.contextmanager
    def bound_states(self, binding: _StateBinding):
        """Route dense() call sites through ``binding`` for the duration
        (``ServeSession``'s per-step state threading)."""
        prev = self._binding
        self._binding = binding
        try:
            yield binding
        finally:
            self._binding = prev

    def hook(self, x: jax.Array, w: jax.Array, tag: str):
        """dense()-hook: route configured projections to the analog path."""
        if self.acfg.backend == "digital":
            return None
        if not any(tag.startswith(l) for l in self.acfg.layers):
            return None
        if self._binding is not None:
            return self._binding.intercept(self, x, w, tag)
        return self.matmul(x, w, tag)
