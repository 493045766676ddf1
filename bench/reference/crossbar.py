"""Plain reference of one emulated crossbar matmul: the weight mapped onto
differential 1T1R tiles, the activations driven dual-rail onto the
wordlines, every computing block evaluated by the Conv4Xbar regression
net (arXiv:2101.07864, Fig. 3 and Table 2) in its plain convolutional
form, and the block groups summed digitally.

Written from the paper and the serving semantics alone; it imports
nothing of the program.  Every contraction of the net runs at the
``precision`` given: ``"highest"`` (f32, as the configurations state the
emulator) in the reference; in a control, ``"high"`` (three bf16 passes,
as ``Precision.HIGH``) or ``"bf16"`` (one pass, as ``Precision.DEFAULT``
on a TPU), spelled out so that they read the same on every platform.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

# the device model of the crossbar (AnalogConfig / CircuitParams defaults)
ROWS = 64                            # wordlines per tile
G_MIN, G_MAX = 1e-6, 1e-4            # S
V_READ = 0.2                         # V
V_TH = 0.08                          # access-transistor threshold, V

GEOMETRIES = {
    # (features, tiles D, rows H, cols W, outputs)
    "rram_ps32_a": (2, 4, 64, 2, 1),
    "rram_ps32_b": (2, 2, 64, 8, 4),
}


def _split(a):
    """f32 -> (bf16 part, bf16 rest), both as f32."""
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def einsum(spec, a, b, precision: str = "highest"):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    dot = lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST)
    if precision == "highest":
        return dot(a, b)
    (ah, al), (bh, bl) = _split(a), _split(b)
    if precision == "bf16":
        return dot(ah, bh)
    if precision == "high":
        return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))
    raise ValueError(precision)


def celu(x):
    return jnp.where(x > 0, x, jnp.expm1(jnp.minimum(x, 0.0)))


def stages(geometry: str):
    """Conv stages of Table 2 as (c_in, c_out, kh, kw, stride_w)."""
    c, d, h, w, _ = GEOMETRIES[geometry]
    out = [(c, 16, 1, 1, 1)]
    for c_in, c_out, k in ((16, 8, 2), (8, 4, 4), (4, 32, 8)):
        k = min(k, h)
        out.append((c_in, c_out, k, 1, 1))
        h //= k
    out.append((32, 32, 1, 2, 1 if w <= 2 else 2))
    return out


def net(params, v, g, periph, precision: str = "highest"):
    """The Conv4Xbar forward on blocks.

    v: (..., D, H) wordline drive of each block (the voltage feature is
    the same on every bitline); g: (..., D, H, W) normalized conductances;
    the leading axes broadcast.  periph: (P,) peripheral features.
    Returns (..., O)."""
    lead = jnp.broadcast_shapes(v.shape[:-2], g.shape[:-3])
    D, H, W = g.shape[-3:]
    # stage 0, pointwise over (V, G): channels last
    w0 = params["conv0_w"][:, :, 0, 0, 0].astype(jnp.float32)   # (16, 2)
    x = (v[..., None, None] * w0[:, 0] + g[..., None] * w0[:, 1]
         + params["conv0_b"])
    x = celu(jnp.broadcast_to(x, lead + (D, H, W, w0.shape[0])))
    x = jnp.swapaxes(x, -3, -2)                        # (..., D, W, H, C)
    i = 1
    while f"conv{i}_w" in params:
        wt = params[f"conv{i}_w"].astype(jnp.float32)  # (O, I, 1, kh, kw)
        kh, kw = wt.shape[3], wt.shape[4]
        if kw == 1:                                    # along the wordlines
            h = x.shape[-2]
            x = x.reshape(x.shape[:-2] + (h // kh, kh, x.shape[-1]))
            x = einsum("...gkc,ock->...go", x, wt[:, :, 0, :, 0], precision)
        else:                                          # across the bitlines
            xw = jnp.swapaxes(x, -3, -2)               # (..., D, H', W, C)
            w_in = xw.shape[-2]
            sw = 1 if w_in <= 2 else 2
            n_out = (w_in - kw) // sw + 1
            taps = [xw[..., j:j + sw * (n_out - 1) + 1:sw, :]
                    for j in range(kw)]
            xw = jnp.stack(taps, axis=-2)              # (..., D, H', Wo, kw, C)
            x = einsum("...wkc,ock->...wo", xw, wt[:, :, 0, 0, :], precision)
            x = jnp.swapaxes(x, -3, -2)                # (..., D, Wo, H', C)
        x = celu(x + params[f"conv{i}_b"])
        i += 1
    # flatten in the (c, d, h, w) order of an NCDHW tensor
    x = jnp.moveaxis(x, -1, -4)                        # (..., C, D, W, H)
    x = jnp.swapaxes(x, -1, -2)                        # (..., C, D, H, W)
    x = x.reshape(x.shape[:-4] + (-1,))
    x = jnp.concatenate(
        [x, jnp.broadcast_to(periph.astype(jnp.float32),
                             x.shape[:-1] + periph.shape)], axis=-1)
    j = 0
    while f"fc{j}_w" in params:
        x = einsum("...i,io->...o", x, params[f"fc{j}_w"], precision) \
            + params[f"fc{j}_b"]
        j += 1
        if f"fc{j}_w" in params:
            x = celu(x)
    return x


def _plan(w, geometry: str):
    """(K, N) weight -> (NB, NO, D, H, W) normalized conductances of the
    differential tiles: w / max|w| mapped into [G_MIN, G_MAX] per rail,
    rows padded with zero conductance, W interleaving (G+, G-) per
    output."""
    _, D, H, W, no = GEOMETRIES[geometry]
    K, N = w.shape
    # programmed in the weights' own precision: each step of the mapping,
    # its constants included, rounds to that type (bf16 for served
    # weights), as a device programmed from bf16 weights would be
    dt = w.dtype
    rnd = lambda a: jnp.asarray(a, jnp.float32).astype(dt).astype(
        jnp.float32)
    span = rnd(G_MAX - G_MIN)
    g_min = rnd(G_MIN)
    wf = w.astype(jnp.float32)
    wn = rnd(wf / jnp.maximum(jnp.max(jnp.abs(wf)), 1e-12))
    gp = rnd(g_min + rnd(span * jnp.clip(wn, 0.0, 1.0)))
    gn = rnd(g_min + rnd(span * jnp.clip(-wn, 0.0, 1.0)))
    kp = -(-K // (H * D)) * H * D
    NP = -(-N // no) * no
    pad = ((0, kp - K), (0, 0))
    gp, gn = jnp.pad(gp, pad), jnp.pad(gn, pad)        # zero conductance
    gp = jnp.pad(gp, ((0, 0), (0, NP - N)))
    gn = jnp.pad(gn, ((0, 0), (0, NP - N)))
    NB, NO = kp // (H * D), NP // no
    g = jnp.stack([gp.reshape(NB, D, H, NO, no),
                   gn.reshape(NB, D, H, NO, no)], axis=-1)
    g = g.reshape(NB, D, H, NO, W).transpose(0, 3, 1, 2, 4)
    return (g - G_MIN) / (G_MAX - G_MIN)              # (NB, NO, D, H, W)


def _drive(x, nb: int, D: int, H: int, overdrive: bool):
    """Dual-rail wordline drives, (2, R, NB, D, H): the magnitude scaled
    by max|x| (with ``overdrive``, lifted above the access transistor's
    threshold), on the rail of the activation's sign; zero stays zero."""
    xs = jnp.maximum(jnp.max(jnp.abs(x)), 1e-9)
    a = jnp.abs(x) / xs
    t = V_TH / V_READ
    u = jnp.where(a > 0.0, t + a * (1.0 - t), 0.0) if overdrive else a
    pos = (x > 0).astype(jnp.float32)
    v = jnp.stack([u * pos, u * (1.0 - pos)])
    v = jnp.pad(v, ((0, 0), (0, 0), (0, nb * D * H - x.shape[1])))
    return v.reshape(2, x.shape[0], nb, D, H), xs


@functools.partial(jax.jit, static_argnames=("geometry", "precision",
                                             "overdrive", "chunk"))
def matmul(x, w, params, *, geometry: str, precision: str = "highest",
           overdrive: bool = True, chunk: int = 0):
    """Emulated (R, K) @ (K, N) in logical units: the rail difference of
    the digitally summed block outputs, scaled back by max|x|."""
    _, D, H, W, no = GEOMETRIES[geometry]
    x = x.astype(jnp.float32)
    R, N = x.shape[0], w.shape[1]
    g = _plan(w, geometry)
    NB, NO = g.shape[:2]
    v, xs = _drive(x, NB, D, H, overdrive)
    periph = jnp.asarray([1.0, 0.0], jnp.float32)
    if not chunk:
        # about 2**27 stage-0 activations per step of the block loop
        chunk = max(1, min(NO, (1 << 27) // (2 * R * NB * D * H * W * 16)))
    NOp = -(-NO // chunk) * chunk
    gc = jnp.pad(g, ((0, 0), (0, NOp - NO)) + ((0, 0),) * 3)
    gc = gc.reshape(NB, NOp // chunk, chunk, D, H, W).transpose(1, 0, 2, 3,
                                                                 4, 5)

    def one(gb):                                      # (NB, chunk, D, H, W)
        y = net(params, v[:, :, :, None], gb[None, None], periph,
                precision)
        return y.sum(axis=2)                          # (2, R, chunk, no)

    y = jax.lax.map(one, gc)                          # (nc, 2, R, chunk, no)
    y = jnp.moveaxis(y, 0, 2).reshape(2, R, NOp * no)[:, :, :N]
    return (y[0] - y[1]) * xs
