"""Plain reference of a served Phi-3.5-MoE decoder (``PhiMoEForCausalLM``
at inference): LayerNorm with bias, biased q/k/v/o projections -- on the
crossbar where the configuration maps them, emulated block by block
(``crossbar.matmul``) -- RoPE on concatenated halves, causal GQA, the
sparsemixer router, each token's experts computed directly from their
weights, and the biased LM head.  Products exact and sums in f32 at
``Precision.HIGHEST``, the activations held in bf16 where the serving
layout holds them, as the configurations state.

Imports nothing of the program.  It reads the benchmark's own weights by
the names of the serving layout, and the routing keys of the
configuration file (``num_experts_per_tok``, ``router_jitter_noise``).

Calls are kept as served: ``prefill`` computes every site over the
call's rows together (their drive scale max|x| couples them), ``decode``
over the step's rows.

``check`` judges a served prefill step by step, each step from the
served values it was given (the program's taps, ``ServeSession.prefill``
with ``taps``), not by one forward from the tokens: with an untrained
Conv4Xbar the emulated q and k reach |x| ~ 100 at the published widths,
the attention is near one-hot, and a key picked differently for one ulp
of q or k moves a row and every later layer's view of it, so two
forwards that round differently part by several logit spreads within
four layers (PERF.md section 2).  Each crossbar site is judged on its
own served drive, where its output is continuous in it.

Routing ties.  Sparsemixer's argmax and mask are discontinuous in the
router logits, which the program and the reference compute with
different roundings, and a flipped expert moves every later row.  So,
given the served routes, the reference follows them, and checks each
served choice against its own from the same earlier choices: the served
choice is admissible if it is the reference's expert with a multiplier
within ``tau / 2`` of the reference's (the most a softmax weight moves
when every logit moves by ``tau``), or with any multiplier in (0, 1]
where some expert lies within ``tau`` logits of its mask threshold; or
another expert within ``tau`` logits of the reference's argmax, with a
multiplier in (0, 1].  ``route_flips`` counts the choices that are not
admissible, and ``routes_replayed`` the admissible ones that differ from
the reference's own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import crossbar as xb
from bench.reference.decoder import NEG_INF, Decoder

# the tie distance of the route replay, in router logits (PERF.md section
# 2 gives its derivation from the CPU readings)
TAU = 0.15


def choice(s: np.ndarray, masked: np.ndarray, eps: float):
    """One sparsemixer choice, in f32 as the model computes it: ``masked``
    holds the logits ``s`` with the earlier choices at -inf, m its max;
    every expert with (m - s_e) / max(|s_e|, m) > 2 eps is masked; the
    choice is the argmax and its multiplier its softmax weight over the
    survivors.  Returns (experts (R,), multipliers (R,), the distance in
    logits of each expert below m (R, E), inf where masked before, and of
    each expert from its mask threshold (R, E), inf where masked before or
    at the argmax)."""
    rows = np.arange(s.shape[0])
    m = masked.max(-1, keepdims=True)
    e = masked.argmax(-1)
    scale = np.maximum(np.abs(s), m)
    live = np.isfinite(masked)
    g = np.where(((m - s) / scale > np.float32(2 * eps)) | ~live, -np.inf,
                 masked)
    p = np.exp(g - m)
    p = p / p.sum(-1, keepdims=True)
    below = np.where(live, m - masked, np.inf)
    margin = np.where(live & (np.arange(s.shape[1]) != e[:, None]),
                      np.abs((m - s) - np.float32(2 * eps) * scale), np.inf)
    return e, p[rows, e].astype(np.float32), below, margin


def sparsemixer(s: np.ndarray, top_k: int, eps: float):
    """Phi-3.5-MoE's router at inference: ``top_k`` choices (``choice``),
    each masking the last, the multipliers not renormalized.  s: (R, E).
    Returns (experts (R, K) int32, multipliers (R, K) f32)."""
    s = np.asarray(s, np.float32)
    masked = s.copy()
    es, ws = [], []
    for _ in range(top_k):
        e, w, _, _ = choice(s, masked, eps)
        es.append(e)
        ws.append(w)
        masked[np.arange(s.shape[0]), e] = -np.inf
    return np.stack(es, -1).astype(np.int32), np.stack(ws, -1)


class PhiMoE(Decoder):
    """``routing``: the configuration file's ``num_experts_per_tok`` and
    ``router_jitter_noise``; ``tau``: the tie distance of the route
    replay, in router logits.  ``held`` and ``precision`` as
    ``Decoder``'s."""

    def __init__(self, model: dict, crossbar: dict, params, eparams,
                 routing: dict, tau: float, **kw):
        super().__init__(model, crossbar, params, eparams, **kw)
        self.top_k = int(routing["num_experts_per_tok"])
        self.jitter = float(routing["router_jitter_noise"])
        self.tau = float(tau)
        self.stats = {"route_flips": 0, "routes_replayed": 0}

    # ------------------------------------------------------------------ #
    def _norm(self, x, p):
        """LayerNorm with bias."""
        xc = x - x.mean(-1, keepdims=True)
        var = (xc * xc).mean(-1, keepdims=True)
        return self.rb(xc * jax.lax.rsqrt(var + self.eps) * p["w"] + p["b"])

    def _biased(self, x2, ap, w: str, b: str, tag: str):
        return self.rb(self.site(x2, ap[w], tag) + ap[b])

    def _expert(self, ff, i: int, e: int, x2):
        w = lambda n: self._f32(ff[n][i, e])
        up = self.rb(xb.einsum("rd,df->rf", x2, w("w_up")))
        gate = self.rb(xb.einsum("rd,df->rf", x2, w("w_gate")))
        h = self.rb(jax.nn.silu(gate) * up)
        return self.rb(xb.einsum("rf,fd->rd", h, w("w_down")))

    def _route(self, s, served):
        """The choices of one layer's rows: the reference's own, or, given
        the served ones, those -- each checked against the reference's
        own choice from the same earlier choices (module docstring)."""
        s = np.asarray(s, np.float32)
        if served is None:
            return sparsemixer(s, self.top_k, self.jitter)
        shape = (s.shape[0], self.top_k)
        se, sw = (np.asarray(a).reshape(shape) for a in served)
        rows = np.arange(s.shape[0])
        masked = s.copy()
        for j in range(self.top_k):
            e, w, below, margin = choice(s, masked, self.jitter)
            got_e, got_w = se[:, j], sw[:, j]
            weight_ok = (got_w > 0) & (got_w <= 1)
            same = got_e == e
            close = np.abs(got_w - w) <= self.tau / 2
            tie = (margin <= self.tau).any(-1)
            ok = np.where(same, close | (tie & weight_ok),
                          (below[rows, got_e] <= self.tau) & weight_ok)
            self.stats["route_flips"] += int((~ok).sum())
            self.stats["routes_replayed"] += int((ok & ~(same & close)).sum())
            masked[rows, got_e] = -np.inf
        return se, sw

    def _moe(self, i: int, h2, served):
        ff = self.params["decoder"]["scan"]["p0"]["ff"]
        s = np.asarray(xb.einsum("rd,de->re", h2, self._f32(ff["router"][i])))
        experts, mult = self._route(s, served)
        got = jnp.zeros(experts.shape + (h2.shape[1],), jnp.float32)
        for e in np.unique(experts):
            r, k = np.nonzero(experts == e)
            got = got.at[r, k].set(self._expert(ff, i, int(e), h2[r]))
        held = self.rb(jnp.asarray(mult))[..., None]
        return self.rb(self.rb(got * held).sum(1)), (experts, mult)

    def _attend_rows(self, q, k, v, mask, prefill: bool):
        """q: (R, Hq, Dh) rows; k, v: (R, T, Hkv, Dh); mask: (R, T).  The
        scores are held in f32, as the serving layout holds them; a
        prefill scales them there and normalizes after mixing (blockwise
        softmax over one KV block), a decode step scales q and normalizes
        before."""
        m = self.m
        g = m["num_heads"] // m["num_kv_heads"]
        qg = q.reshape(q.shape[0], m["num_kv_heads"], g, -1)
        scale = m["head_dim"] ** -0.5
        if prefill:
            sc = xb.einsum("rhgd,rthd->rhgt", qg, k) * np.float32(scale)
        else:
            sc = xb.einsum("rhgd,rthd->rhgt",
                           self.rb(qg * self.rb(jnp.float32(scale))), k)
        sc = jnp.where(mask[:, None, None, :], sc, NEG_INF)
        p = jnp.exp(sc - sc.max(-1, keepdims=True))
        if prefill:
            o = self.rb(xb.einsum("rhgt,rthd->rhgd", self.rb(p), v))
            o = o / p.sum(-1)[..., None]
        else:
            o = xb.einsum("rhgt,rthd->rhgd",
                          self.rb(p / p.sum(-1, keepdims=True)), v)
        return self.rb(o).reshape(q.shape[0], -1)

    def _mix(self, q, k, v, pos, prefill: bool):
        """The attention of rows q (B, S, Hq, Dh) at positions pos (B, S)
        over keys and values (B, T, Hkv, Dh), causal.  Returns (B*S,
        Hq*Dh)."""
        B, S = q.shape[:2]
        T = k.shape[1]
        rows = lambda a: jnp.repeat(a, S, axis=0)          # per query row
        mask = np.arange(T)[None, :] <= np.asarray(pos).reshape(-1, 1)
        return self._attend_rows(q.reshape(B * S, *q.shape[2:]), rows(k),
                                 rows(v), mask, prefill)

    def attention(self, i: int, x, pos, cache, prefill: bool, taps: dict):
        """Layer ``i``'s attention block on its input x (B, S, D) at
        positions pos (B, S), after ``cache`` ((k, v) of (B, T, Hkv, Dh)
        ending where the rows begin, or None); its steps kept in ``taps``
        under the program's names.  Returns (the FFN block's input, the
        layer's (k, v) with the rows')."""
        m = self.m
        B, S, _ = x.shape
        ap = self._layer("attn", i)
        h = self._norm(x, self._layer("norm1", i)).reshape(B * S, -1)
        bs = lambda a: a.reshape(B, S, -1)         # as the program taps
        y = {}
        for t in ("q", "k", "v"):
            taps[f"attn.{t}:in"] = bs(h)
            y[t] = self.site(h, ap["w" + t], "attn." + t)
            taps[f"attn.{t}"] = bs(y[t])
        q, k, v = (self.rb(y[t] + ap["b" + t]) for t in ("q", "k", "v"))
        q = self._rope(q.reshape(B, S, m["num_heads"], -1), pos)
        k = self._rope(k.reshape(B, S, m["num_kv_heads"], -1), pos)
        v = v.reshape(B, S, m["num_kv_heads"], -1)
        taps["attn.q_rot"] = q
        if cache is not None:
            k = jnp.concatenate([cache[0], k], axis=1)
            v = jnp.concatenate([cache[1], v], axis=1)
        o = self._mix(q, k, v, pos, prefill)
        taps["attn.o:in"] = bs(o)
        y["o"] = self.site(o, ap["wo"], "attn.o")
        taps["attn.o"] = bs(y["o"])
        out = self.rb(y["o"] + ap["bo"]).reshape(B, S, -1)
        return self.rb(x + out), (k, v)

    def ffn(self, i: int, x, served, taps: dict):
        """Layer ``i``'s expert block on its input x (B, S, D); ``served``
        its routes (experts, multipliers) of (B, S, K), or None; its steps
        kept in ``taps``.  Returns the layer's output."""
        B, S, _ = x.shape
        h2 = self._norm(x, self._layer("norm2", i))
        y = self._expert_block(i, h2, served, taps)
        return self.rb(x + y)

    def _expert_block(self, i: int, h2, served, taps: dict):
        """The expert block from its input h2 (B, S, D), the routes
        followed where ``served`` (module docstring).  Returns (B, S,
        D)."""
        B, S, _ = h2.shape
        y, (e, w) = self._moe(i, h2.reshape(B * S, -1), served)
        taps.update({"moe.in": h2, "moe.experts": e.reshape(B, S, -1),
                     "moe.weights": np.asarray(w).reshape(B, S, -1),
                     "moe.out": y.reshape(B, S, -1)})
        return taps["moe.out"]

    def _layers(self, x, pos, cache, served, prefill: bool):
        """The whole stack, each layer's blocks on the last one's output.
        Returns (x, new cache, taps of each layer)."""
        new_cache, layers = [], []
        for i in range(self.m["num_layers"]):
            taps = {"x_in": x}
            x, kv = self.attention(i, x, pos, None if cache is None
                                   else cache[i], prefill, taps)
            new_cache.append(kv)
            x = self.ffn(i, x, None if served is None
                         else (served[0][i], served[1][i]), taps)
            layers.append(taps)
        return x, new_cache, layers

    def _head(self, x, taps: dict):
        """The final norm and the biased head on the last layer's output
        x (R, D)."""
        fn = jax.tree.map(self._f32, self.params["final_norm"])
        h = self._norm(x, fn)
        taps["final"], taps["lm_head:in"] = x, h
        return self._logits_of(h)

    def _logits_of(self, h):
        """The biased head on the final norm's output h (R, D)."""
        out = self.rb(xb.einsum("rd,dv->rv", h, self.params["head"]))
        out = self.rb(out + self._f32(self.params["head_bias"]))
        return out[:, :self.m["vocab_size"]]

    # ------------------------------------------------------------------ #
    def prefill(self, tokens, served=None):
        """One batched prefill of ``tokens`` (B, S) from position 0, each
        layer on the last one's output.  ``served``: the program's routes
        (experts, multipliers), each (L, B, S, K), followed and checked.
        Returns (logits (B, S, V), cache: per layer (k, v) of (B, S, Hkv,
        Dh), taps as ``ServeSession.prefill`` hands them back)."""
        tokens = np.asarray(tokens)
        B, S = tokens.shape
        pos = np.broadcast_to(np.arange(S), (B, S))
        x, cache, layers = self._layers(self._embed(tokens), pos, None,
                                        served, prefill=True)
        taps = {"layers": layers}
        logits = self._head(x.reshape(B * S, -1), taps)
        return np.asarray(logits).reshape(B, S, -1), cache, taps

    def decode(self, tokens, cache, pos: int, served=None):
        """One batched decode step: ``tokens`` (B, 1) at position ``pos``
        after ``cache`` (``prefill``'s, or the last step's).  Returns
        (logits (B, V), cache)."""
        tokens = np.asarray(tokens).reshape(-1, 1)
        B = tokens.shape[0]
        p = np.full((B, 1), pos)
        x, cache, _ = self._layers(self._embed(tokens), p, cache, served,
                                   prefill=False)
        return np.asarray(self._head(x.reshape(B, -1), {})), cache

    def check(self, tokens, cache, taps):
        """A served prefill of ``tokens`` (B, S), each step from its own
        served inputs: ``cache`` per layer (k, v) of (B, S, Hkv, Dh) and
        ``taps`` as ``ServeSession.prefill`` hands them back.  Returns
        pairs (served, reference) of (rows, width) under:
          "site": every crossbar site of every layer, the reference's site
            applied to the served drive (``<tag>:in``);
          "mix": per layer, the attention of the served rotated q and the
            served keys and values, against the o site's served drive;
          "ffn": per layer, the expert block of its served input with the
            served routes followed and checked (``route_flips``);
          "glue": the embedding, the norms, the biases and RoPE of q, and
            the residual adds, each from the served values before it;
        "kv": per layer the reference's (k, v) from the served k and v
        sites, and "logits" (B*S, V) from the served head input."""
        tokens = np.asarray(tokens)
        B, S = tokens.shape
        pos = np.broadcast_to(np.arange(S), (B, S))
        got = lambda a: jnp.asarray(np.asarray(a, np.float32))
        rows = lambda a: jnp.asarray(a).reshape(B * S, -1)
        m = self.m
        out = {"site": [], "mix": [], "ffn": [], "glue": [], "kv": []}
        L = m["num_layers"]
        layers = [{k: got(v) for k, v in t.items()} for t in taps["layers"]]
        out["glue"].append((layers[0]["x_in"], self._embed(tokens)))
        for i, t in enumerate(layers):
            ap = self._layer("attn", i)
            x = t["x_in"]
            out["glue"].append((t["attn.q:in"], self._norm(
                x, self._layer("norm1", i))))
            for s in ("q", "k", "v", "o"):
                out["site"].append((t[f"attn.{s}"], self.site(
                    rows(t[f"attn.{s}:in"]), ap["w" + s], "attn." + s)))
            q = self._rope(self.rb(t["attn.q"] + ap["bq"]).reshape(
                B, S, m["num_heads"], -1), pos)
            out["glue"].append((t["attn.q_rot"], q))
            k = self._rope(self.rb(t["attn.k"] + ap["bk"]).reshape(
                B, S, m["num_kv_heads"], -1), pos)
            v = self.rb(t["attn.v"] + ap["bv"]).reshape(B, S,
                                                         m["num_kv_heads"], -1)
            out["kv"].append((k, v))
            ck, cv = (got(c) for c in cache[i])
            out["mix"].append((t["attn.o:in"], self._mix(
                t["attn.q_rot"], ck, cv, pos, prefill=True)))
            mid = self.rb(x + self.rb(t["attn.o"] + ap["bo"]))
            out["glue"].append((t["moe.in"], self._norm(
                mid, self._layer("norm2", i))))
            y = self._expert_block(i, t["moe.in"], (
                np.asarray(taps["layers"][i]["moe.experts"]),
                np.asarray(taps["layers"][i]["moe.weights"])), {})
            out["ffn"].append((t["moe.out"], y))
            nxt = got(taps["final"]) if i == L - 1 else layers[i + 1]["x_in"]
            out["glue"].append((nxt, self.rb(mid + t["moe.out"])))
        fn = jax.tree.map(self._f32, self.params["final_norm"])
        out["glue"].append((got(taps["lm_head:in"]),
                            self._norm(got(taps["final"]), fn)))
        out["logits"] = np.asarray(self._logits_of(
            rows(got(taps["lm_head:in"]))))
        for k in ("site", "mix", "ffn", "glue"):
            out[k] = [(rows(a), rows(b)) for a, b in out[k]]
        return out
