"""Plain reference of a served dense decoder-only transformer, with the
projections that a configuration maps onto the crossbar emulated block
by block (``crossbar.matmul``); products exact and sums in f32, the
activations held in bf16 where the serving layout holds them, as the
configurations state (``Decoder``).

Imports nothing of the program.  It reads the benchmark's own weights
(made from the seed by ``bench.harness.make_weights``) by the names of
the serving layout, and follows the published layer equations with the
serving semantics that the configuration file states under ``assumed``:
RoPE on concatenated halves, GQA, RMSNorm and a gated SiLU MLP.

What couples the rows of one call is reproduced by computing them
together: the drive scale of every crossbar site is the max |x| over the
call's rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import crossbar as xb

NEG_INF = -1e30
FP8_MAX = 448.0                      # largest finite float8_e4m3fn


class Decoder:
    """``model``: the configuration file's ``model`` block;
    ``params``: the served weight tree; ``eparams``: the Conv4Xbar
    params of the crossbar sites.

    ``held``: the type each activation is held in where the serving
    layout holds one (each projection's, norm's and attention's output,
    the residual stream, RoPE, the attention scores and probabilities,
    the key/value cache): ``"bf16"``, as the configurations state, or
    ``"fp8"`` (float8 e4m3 at a per-tensor scale) in a control.
    ``precision``: that of the crossbar net's contractions, ``"highest"``
    (f32, as the configurations state) in the reference, a lower one in a
    control.  Every digital contraction runs at HIGHEST."""

    def __init__(self, model: dict, crossbar: dict, params, eparams,
                 held: str = "bf16", precision: str = "highest"):
        assert held in ("bf16", "fp8"), held
        self.m = model
        self.precision = precision
        self.held = held
        self.layers = tuple(crossbar.get("layers", ()))
        self.geometry = crossbar.get("geometry")
        self.overdrive = crossbar.get("wl_overdrive", True)
        self.params, self.eparams = params, eparams
        self.eps = model["norm_eps"]

    # ------------------------------------------------------------------ #
    def rb(self, x):
        """An activation as the serving layout holds it."""
        x = jnp.asarray(x, jnp.float32)
        if self.held == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s

    def _f32(self, a):
        return jnp.asarray(a).astype(jnp.float32)

    def _layer(self, name: str, i: int):
        tree = self.params["decoder"]["scan"]["p0"][name]
        return jax.tree.map(lambda a: self._f32(a[i]), tree)

    def _norm(self, x, p):
        return self.rb(x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + self.eps) * p["w"])

    def _rope(self, x, pos):
        """x: (..., H, Dh), pos broadcastable to x.shape[:-2]."""
        half = x.shape[-1] // 2
        freqs = self.m["rope_theta"] ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.asarray(pos, jnp.float32)[..., None, None] * freqs
        c, s = self.rb(jnp.cos(ang)), self.rb(jnp.sin(ang))
        x1, x2 = x[..., :half], x[..., half:]
        return self.rb(jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                                       axis=-1))

    def site(self, x2, w, tag: str):
        """One projection of a group's rows x2: (R, K)."""
        if any(tag.startswith(l) for l in self.layers):
            # the served weights are bf16: their conductances are
            # programmed from the bf16 values (``crossbar._plan``)
            return self.rb(xb.matmul(x2, w.astype(jnp.bfloat16),
                                     self.eparams, geometry=self.geometry,
                                     precision=self.precision,
                                     overdrive=self.overdrive))
        return self.rb(xb.einsum("rk,kn->rn", x2, w))

    def _qkv(self, ap, h2):
        m = self.m
        q = self.site(h2, ap["wq"], "attn.q")
        k = self.site(h2, ap["wk"], "attn.k")
        v = self.site(h2, ap["wv"], "attn.v")
        hd = m["head_dim"]
        return (q.reshape(-1, m["num_heads"], hd),
                k.reshape(-1, m["num_kv_heads"], hd),
                v.reshape(-1, m["num_kv_heads"], hd))

    def _attend(self, q, k, v, mask):
        """q: (R, Hq, Dh); k, v: (R, T, Hkv, Dh); mask: (R, T)."""
        m = self.m
        g = m["num_heads"] // m["num_kv_heads"]
        qg = q.reshape(q.shape[0], m["num_kv_heads"], g, -1)
        scale = self.rb(jnp.float32(m["head_dim"] ** -0.5))
        s = self.rb(xb.einsum("rhgd,rthd->rhgt", self.rb(qg * scale), k))
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
        e = jnp.exp(s - s.max(-1, keepdims=True))
        o = xb.einsum("rhgt,rthd->rhgd",
                      self.rb(e / e.sum(-1, keepdims=True)), v)
        return self.rb(o).reshape(q.shape[0], -1)

    def _ffn(self, fp, h2):
        up = self.site(h2, fp["w_up"], "mlp.up")
        gate = self.site(h2, fp["w_gate"], "mlp.gate")
        return self.site(self.rb(jax.nn.silu(gate) * up), fp["w_down"],
                         "mlp.down")

    def _logits(self, x):
        fn = jax.tree.map(self._f32, self.params["final_norm"])
        out = self.rb(xb.einsum("rd,dv->rv", self._norm(x, fn),
                                self.params["head"]))
        return out[:, :self.m["vocab_size"]]

    def _embed(self, tokens):
        return self._f32(jnp.take(self.params["embed"],
                                  jnp.asarray(tokens), axis=0))

    # ------------------------------------------------------------------ #
    def tick(self, seqs, positions) -> np.ndarray:
        """One batched decode call: row r feeds token ``seqs[r][p]`` at
        position ``p = positions[r]`` after ``seqs[r][:p]``.  Returns
        (R, V).  The history's keys and values are recomputed from its
        tokens, which is exact while one layer holds digital attention
        (later layers' history depends on how earlier calls grouped
        their rows)."""
        m = self.m
        assert m["num_layers"] == 1 and not any(
            "attn.q".startswith(l) for l in self.layers), \
            "tick() needs one layer with digital attention"
        R = len(seqs)
        T = max(int(p) for p in positions) + 1
        toks = np.zeros((R, T), np.int32)
        for r, (s, p) in enumerate(zip(seqs, positions)):
            toks[r, :p + 1] = np.asarray(s)[:p + 1]
        pos = np.asarray(positions)
        ap = self._layer("attn", 0)
        n1 = self._layer("norm1", 0)
        # history: every position of every row, digital projections
        hh = self._norm(self._embed(toks).reshape(R * T, -1), n1)
        kh = self.site(hh, ap["wk"], "attn.k").reshape(R, T,
                                                       m["num_kv_heads"], -1)
        vh = self.site(hh, ap["wv"], "attn.v").reshape(R, T,
                                                       m["num_kv_heads"], -1)
        kh = self._rope(kh, np.broadcast_to(np.arange(T), (R, T)))
        x = self._embed(toks[np.arange(R), pos])
        h = self._norm(x, n1)
        q = self.site(h, ap["wq"], "attn.q").reshape(R, m["num_heads"], -1)
        q = self._rope(q, pos)
        mask = np.arange(T)[None, :] <= pos[:, None]
        o = self._attend(q, kh, vh, mask)
        x = self.rb(x + self.site(o, ap["wo"], "attn.o"))
        h2 = self._norm(x, self._layer("norm2", 0))
        x = self.rb(x + self._ffn(self._layer("ff", 0), h2))
        return np.asarray(self._logits(x))
