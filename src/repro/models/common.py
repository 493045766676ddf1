"""Shared model infrastructure: parameter schemas (one source of truth for
shapes / shardings / init), mesh context, norms, activations, RoPE.

No flax: a module is (schema builder, pure apply fn). From a schema we derive
  * real params        (tests, small-scale training)
  * ShapeDtypeStructs  (dry-run lowering -- nothing allocated)
  * PartitionSpec tree (in_shardings / sharding constraints)
"""
from __future__ import annotations

import contextlib
import threading
import zlib
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# --------------------------------------------------------------------------- #
# Mesh context
# --------------------------------------------------------------------------- #
class _MeshState(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None


_STATE = _MeshState()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = _STATE.mesh
    _STATE.mesh = mesh
    try:
        yield mesh
    finally:
        _STATE.mesh = prev


def current_mesh() -> Optional[Mesh]:
    return _STATE.mesh


def dp_axes() -> Tuple[str, ...]:
    """Axes the global batch is sharded over."""
    mesh = current_mesh()
    if mesh is None:
        return ()
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(name) -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    if isinstance(name, (tuple, list)):
        n = 1
        for a in name:
            n *= axis_size(a)
        return n
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def _sanitize_spec(shape: Tuple[int, ...], spec: P) -> P:
    """Drop spec axes that are absent from the mesh or don't divide the dim."""
    mesh = current_mesh()
    present = set(mesh.axis_names) if mesh is not None else set()

    def keep_axes(ax):
        if ax is None:
            return None
        axes = ax if isinstance(ax, (tuple, list)) else (ax,)
        axes = tuple(a for a in axes if a in present)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]

    entries = [keep_axes(a) for a in spec] + [None] * (len(shape) - len(spec))
    out = []
    for dim, ax in zip(shape, entries):
        if ax is None or axis_size(ax) <= 1 or dim % axis_size(ax) != 0:
            out.append(None)
        else:
            out.append(ax)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint against the context mesh (no-op without one).

    Entries may be None, an axis name, or a tuple of axis names. The special
    string "dp" expands to the batch axes of the current mesh.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    entries = tuple(dp_axes() if e == "dp" else e for e in spec_entries)
    spec = _sanitize_spec(x.shape, P(*entries))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# --------------------------------------------------------------------------- #
# Parameter schemas
# --------------------------------------------------------------------------- #
class ParamSchema(NamedTuple):
    shape: Tuple[int, ...]
    spec: P
    init: str = "normal"        # normal | zeros | ones | embed
    scale: float = 1.0          # stddev for "normal"
    dtype: Any = jnp.float32


def dense_schema(d_in: int, d_out: int, *, fsdp="data", tp="model",
                 scale: Optional[float] = None) -> ParamSchema:
    """2-D (FSDP x TP) sharded projection weight."""
    s = scale if scale is not None else d_in ** -0.5
    return ParamSchema((d_in, d_out), P(fsdp, tp), "normal", s)


def is_schema_leaf(x) -> bool:
    return isinstance(x, ParamSchema)


def _tree_map(fn, schema):
    return jax.tree.map(fn, schema, is_leaf=is_schema_leaf)


def stack_schema(schema, n: int):
    """Add a leading stacked-layers dim of size n to every leaf."""
    def f(p: ParamSchema) -> ParamSchema:
        return ParamSchema((n,) + p.shape, P(None, *p.spec), p.init, p.scale, p.dtype)
    return _tree_map(f, schema)


def init_params(key: jax.Array, schema, dtype=jnp.float32):
    """Materialize real parameters (path-deterministic key folding)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        schema, is_leaf=is_schema_leaf)

    def init_one(path, p: ParamSchema):
        # crc32, NOT hash(): str hashing is salted per interpreter run,
        # which made every process draw DIFFERENT params for the same
        # seed and broke cross-process round trips (--state-save/-load)
        k = jax.random.fold_in(key, zlib.crc32(
            jax.tree_util.keystr(path).encode()) & 0x7FFFFFFF)
        dt = p.dtype if p.dtype != jnp.float32 else dtype
        if p.init == "zeros":
            return jnp.zeros(p.shape, dt)
        if p.init == "ones":
            return jnp.ones(p.shape, dt)
        if p.init == "embed":
            return (jax.random.normal(k, p.shape, jnp.float32) * p.scale).astype(dt)
        return (jax.random.normal(k, p.shape, jnp.float32) * p.scale).astype(dt)

    vals = [init_one(path, p) for path, p in leaves]
    return jax.tree.unflatten(treedef, vals)


def spec_tree(schema):
    return _tree_map(lambda p: p.spec, schema)


def abstract_params(schema, mesh: Optional[Mesh] = None, dtype=jnp.float32):
    """ShapeDtypeStructs (+ NamedShardings) -- for AOT lowering."""
    def f(p: ParamSchema):
        dt = p.dtype if p.dtype != jnp.float32 else dtype
        if mesh is None:
            return jax.ShapeDtypeStruct(p.shape, dt)
        spec = _sanitize_spec(p.shape, p.spec)
        return jax.ShapeDtypeStruct(p.shape, dt, sharding=NamedSharding(mesh, spec))
    return _tree_map(f, schema)


def sharding_tree(schema, mesh: Mesh):
    def f(p: ParamSchema):
        return NamedSharding(mesh, _sanitize_spec(p.shape, p.spec))
    return _tree_map(f, schema)


def param_count(schema) -> int:
    leaves = jax.tree.leaves(schema, is_leaf=is_schema_leaf)
    return int(sum(int(np.prod(p.shape)) for p in leaves))


# --------------------------------------------------------------------------- #
# Abstract arrays helper (activations / caches)
# --------------------------------------------------------------------------- #
def abstract_array(shape, dtype, spec: P, mesh: Optional[Mesh]):
    if mesh is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, _sanitize_spec(tuple(shape), spec)))


# --------------------------------------------------------------------------- #
# Dense hook: routes matmuls through an alternative executor (the SEMULATOR
# analog backend installs itself here; default is a plain einsum).
# --------------------------------------------------------------------------- #
class _HookState(threading.local):
    def __init__(self):
        self.fn = None


_HOOK = _HookState()


@contextlib.contextmanager
def use_dense_hook(fn):
    prev = _HOOK.fn
    _HOOK.fn = fn
    try:
        yield
    finally:
        _HOOK.fn = prev


def dense(x: jax.Array, w: jax.Array, tag: str = "") -> jax.Array:
    """y = x @ w over the last dim of x; interceptable by the analog backend.
    Tapped (``tap``) as ``<tag>:in`` and ``<tag>``."""
    out = _HOOK.fn(x, w, tag) if _HOOK.fn is not None else None
    if out is None:
        out = jnp.einsum("...k,kf->...f", x, w.astype(x.dtype))
    if tag:
        tap(tag + ":in", x)
        tap(tag, out)
    return out


# --------------------------------------------------------------------------- #
# Taps: named intermediate values that a check of the served model reads
# (each crossbar site's drive and output, a layer's input, the routes).
# ``tap`` is a no-op unless a ``tapping`` collector is open, so a program
# traced without one carries none of them.  A scanned layer's taps leave
# the scan beside its cache (``models.model._run_stack``).
# --------------------------------------------------------------------------- #
class _TapState(threading.local):
    def __init__(self):
        self.taps = None


_TAP = _TapState()


def tap(name: str, value) -> None:
    """Keep ``value`` under ``name`` in the open collector, if any."""
    if _TAP.taps is not None:
        _TAP.taps[name] = value


def tapping_on() -> bool:
    return _TAP.taps is not None


@contextlib.contextmanager
def tapping(on: bool = True):
    """A fresh collector for the ``tap``s made inside (yielded), or None
    and nothing collected when not ``on``."""
    prev = _TAP.taps
    _TAP.taps = {} if on else prev
    try:
        yield _TAP.taps if on else None
    finally:
        _TAP.taps = prev


# --------------------------------------------------------------------------- #
# Scan-states channel: lets the model's lax.scan over layer periods thread
# per-period DeploymentStates.  The provider (an analog _StateBinding)
# exposes:
#   recording          -- True while discovering call sites (period loop is
#                         Python-unrolled so dense() sees concrete weights)
#   scan_record(g, p)  -- context: record period p of scan group g
#   scan_pick(g, n)    -- function of the traced period index returning
#                         that period's state pytree, or None when group g
#                         has no bound states
#   scan_slice(g, ls)  -- context: serve the scan body's current period
#                         from the traced per-period states ls
# The model never imports the analog layer; it only calls this protocol.
# --------------------------------------------------------------------------- #
class _ScanStatesState(threading.local):
    def __init__(self):
        self.provider = None


_SCAN_STATES = _ScanStatesState()


@contextlib.contextmanager
def use_scan_states(provider):
    prev = _SCAN_STATES.provider
    _SCAN_STATES.provider = provider
    try:
        yield provider
    finally:
        _SCAN_STATES.provider = prev


def scan_states_provider():
    return _SCAN_STATES.provider


# --------------------------------------------------------------------------- #
# Numerics
# --------------------------------------------------------------------------- #
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def norm_schema(d: int, kind: str):
    if kind == "layernorm":
        return {"w": ParamSchema((d,), P(None), "ones"),
                "b": ParamSchema((d,), P(None), "zeros")}
    return {"w": ParamSchema((d,), P(None), "ones")}


def apply_norm(params, x, kind: str):
    if kind == "layernorm":
        return layernorm(x, params["w"], params["b"])
    return rmsnorm(x, params["w"])


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu,
            "celu": jax.nn.celu}[name]


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, base: float) -> jax.Array:
    return base ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32) / (head_dim // 2))


def apply_rope(x: jax.Array, positions: jax.Array, base: float) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: broadcastable to (..., S).

    Angles/sin/cos are computed in fp32 (position precision), but the
    rotation products stay in x's dtype so sharded activations never float
    through the collective layer as fp32 (2x bytes)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, base)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, D/2)
    if x.ndim == ang.ndim + 1:                        # head axis present
        ang = ang[..., None, :]
    cos = jnp.cos(ang).astype(x.dtype)
    sin = jnp.sin(ang).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
