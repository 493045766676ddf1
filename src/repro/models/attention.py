"""Attention: GQA projections + blockwise-softmax ("flash" in pure JAX)
variants. No S x S materialization anywhere.

Train/prefill use a FLAT-HEAD layout (B, S, Hq, D) with KV repeated to Hq
heads at compute time, so tensor parallelism can shard the head dim whenever
Hq divides the model axis (qwen 64H, command-r 96H, internvl 64H, phi 32H,
seamless 16H). When it doesn't (gemma3 4H, recurrentgemma 10H, llama4 40H,
deepseek 56H), attention falls back to *sequence* sharding of the query dim
over the model axis (context parallelism) with the (small, GQA) KV gathered.
The choice is automatic via divisibility; both are expressed as sharding
constraints, never shard_map, so XLA owns the collective schedule.

Decode keeps the grouped (B, S, Hkv, D) cache layout (no KV repeat in
memory) and can shard the cache *sequence* dim over the model axis with an
explicit shard_map flash-decode (partial-softmax combine).

Variants
  global  : causal, blockwise scan over KV blocks
  local   : exact sliding window via the 2-chunk trick
  chunked : llama4-style intra-chunk causal attention (1-chunk trick)
  bidir   : encoder self attention (no mask)
  cross   : encoder-decoder cross attention (no mask)
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ArchConfig, ParallelConfig, GLOBAL_ATTN,
                                LOCAL_ATTN, CHUNKED_ATTN, BIDIR_ATTN)
from repro.models.common import (ParamSchema, apply_norm, apply_rope,
                                 axis_size, current_mesh, dense, dense_schema,
                                 dp_axes, norm_schema, shard, tap)

NEG_INF = -1e30


def _scores(spec, q, k):
    """Attention scores in f32, produced once: a bf16 score read by the
    running max and by the exponent may be rounded in one fusion and not
    in the other (XLA's excess precision), and with scores spread over
    6e4 -- an untrained crossbar emulator's q and k reach |x| ~ 100 --
    exp(s - max) then overflows (non-finite logits on a TPU v5e)."""
    return jnp.einsum(spec, q, k, preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# Schema
# --------------------------------------------------------------------------- #
def attention_schema(cfg: ArchConfig, *, cross: bool = False):
    d, qf = cfg.d_model, cfg.num_heads * cfg.head_dim
    kvf = cfg.num_kv_heads * cfg.head_dim
    s = {
        "wq": dense_schema(d, qf),
        "wk": dense_schema(d, kvf),
        "wv": dense_schema(d, kvf),
        "wo": dense_schema(qf, d, fsdp="model", tp="data"),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = ParamSchema((qf,), P("model"), "zeros")
        s["bk"] = ParamSchema((kvf,), P(None), "zeros")
        s["bv"] = ParamSchema((kvf,), P(None), "zeros")
    if cfg.o_bias and not cross:
        s["bo"] = ParamSchema((d,), P(None), "zeros")
    if cfg.qk_norm:
        s["qnorm"] = norm_schema(cfg.head_dim, "rmsnorm")
        s["knorm"] = norm_schema(cfg.head_dim, "rmsnorm")
    return s


def _head_tp(cfg: ArchConfig) -> bool:
    tp = axis_size("model")
    return cfg.num_heads % tp == 0


def _shard_flat(x, cfg, *trailing):
    """Shard (B, S, H, ...) on heads if divisible else on S."""
    if _head_tp(cfg):
        return shard(x, "dp", None, "model", *trailing)
    return shard(x, "dp", "model", None, *trailing)


# --------------------------------------------------------------------------- #
# Projections
# --------------------------------------------------------------------------- #
def _project_q(params, x, cfg: ArchConfig):
    """-> (B, S, Hq, D) flat heads."""
    B, S, _ = x.shape
    q = dense(x, params["wq"], "attn.q")
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    if "qnorm" in params:
        q = apply_norm(params["qnorm"], q, "rmsnorm")
    return q


def _project_kv(params, x, cfg: ArchConfig):
    """-> (B, S, Hkv, D) grouped."""
    B, S, _ = x.shape
    k = dense(x, params["wk"], "attn.k")
    v = dense(x, params["wv"], "attn.v")
    if "bk" in params:
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if "knorm" in params:
        k = apply_norm(params["knorm"], k, "rmsnorm")
    return k, v


def _repeat_kv(k, cfg: ArchConfig):
    """(B,S,Hkv,D) -> (B,S,Hq,D). Under head sharding each device only
    materializes its own head slice of the broadcast."""
    g = cfg.num_heads // cfg.num_kv_heads
    if g == 1:
        return k
    B, S, Hkv, D = k.shape
    k = jnp.broadcast_to(k[:, :, :, None], (B, S, Hkv, g, D))
    return k.reshape(B, S, Hkv * g, D)


def _out_proj(params, o, cfg: ArchConfig):
    B, S = o.shape[:2]
    o = o.reshape(B, S, cfg.num_heads * cfg.head_dim)
    if not _head_tp(cfg) and S > 1:
        # seq-TP case: gather the (bf16) attention output over the model axis
        # once, so the out-projection contracts an unsharded dim (XLA would
        # otherwise emit a fp32 all-reduce of the residual stream).
        o = shard(o, "dp", None, None)
    out = dense(o, params["wo"], "attn.o")
    if "bo" in params:
        out = out + params["bo"].astype(out.dtype)
    return out


def _mixer_gather(x, pcfg, mode):
    if pcfg.residual_seq_shard and mode != "decode":
        return shard(x, "dp", None, None)
    return x


# --------------------------------------------------------------------------- #
# Blockwise softmax core (flat heads)
# --------------------------------------------------------------------------- #
def flash_attention(q, k, v, *, causal: bool, q_offset=0, k_offset=0,
                    block_kv: int = 1024, shard_hint=None,
                    window: int = 0, chunk: int = 0):
    """q: (B,Sq,H,D); k,v: (B,Sk,H,D) (already head-repeated).
    shard_hint: None | "heads" | "seq" -- where the model axis lives.
    window/chunk add sliding-window / same-chunk masking (dense fallback for
    shapes the exact windowed paths can't tile). Returns (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    bk = min(block_kv, Sk)
    if Sk % bk != 0:                   # pad KV; padded keys are masked out
        pad = bk - Sk % bk
        k = jnp.concatenate([k, jnp.zeros((B, pad, H, D), k.dtype)], axis=1)
        v = jnp.concatenate([v, jnp.zeros((B, pad, H, D), v.dtype)], axis=1)
    kv_len = Sk
    Sk = k.shape[1]
    nb = Sk // bk
    q_pos = q_offset + jnp.arange(Sq)

    def c_spec(*tail):  # carry spec for (B, H, Sq, *tail)
        if shard_hint == "heads":
            return ("dp", "model", None) + tail
        if shard_hint == "seq":
            return ("dp", None, "model") + tail
        return ("dp", None, None) + tail

    kr = k.reshape(B, nb, bk, H, D).transpose(1, 0, 2, 3, 4)
    vr = v.reshape(B, nb, bk, H, D).transpose(1, 0, 2, 3, 4)
    blk_start = k_offset + jnp.arange(nb) * bk

    init = (shard(jnp.full((B, H, Sq), NEG_INF, jnp.float32), *c_spec()),
            shard(jnp.zeros((B, H, Sq), jnp.float32), *c_spec()),
            shard(jnp.zeros((B, H, Sq, D), jnp.float32), *c_spec(None)))

    def body(carry, xs):
        kb, vb, start = xs
        m, l, o = carry
        # scaled in f32: the bf16 operands are the rotated q and k as the
        # layer holds them (no second rounding of q)
        s = _scores("bqhd,bkhd->bhqk", q, kb) * (D ** -0.5)
        k_pos = start + jnp.arange(bk)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
            if window:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
            if chunk:
                mask &= (q_pos[:, None] // chunk) == (k_pos[None, :] // chunk)
            s = jnp.where(mask[None, None], s, NEG_INF)
        elif kv_len != Sk:             # mask padded keys in the bidir case
            mask = (k_pos < k_offset + kv_len)[None, None, None]
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb.dtype), vb)
        o_new = o * alpha[..., None] + pv.astype(jnp.float32)
        return (m_new, l_new, o_new), None

    # remat the per-block body: backward recomputes one score block at a
    # time instead of stashing the full (B,H,Sq,Sk) score tensor
    body = jax.checkpoint(body)
    (m, l, o), _ = jax.lax.scan(body, init, (kr, vr, blk_start))
    o = o / jnp.maximum(l, 1e-20)[..., None]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(v.dtype)   # (B,Sq,H,D)


def _grouped_windowed(q, k, v, w: int, *, sliding: bool):
    """Shared core for local (sliding=True) and llama4-chunked (False)
    attention over (B,S,H,D) inputs, reshaped to window chunks.

    Model-axis sharding, by divisibility:
      H % tp == 0        -> 5D (B,n,H,w,D) sharded on heads
      (n*H) % tp == 0    -> 4D (B,G=n*H,w,D) sharded on the merged group dim
      else               -> replicated over the model axis
    """
    B, S, H, D = q.shape
    n = S // w
    G = n * H
    tp = axis_size("model")

    def to5(x):  # (B,S,H,D) -> (B,n,H,w,D)
        return x.reshape(B, n, w, H, D).transpose(0, 1, 3, 2, 4)

    q5, k5, v5 = to5(q), to5(k), to5(v)
    if sliding:
        kp = jnp.concatenate([jnp.zeros_like(k5[:, :1]), k5[:, :-1]], axis=1)
        vp = jnp.concatenate([jnp.zeros_like(v5[:, :1]), v5[:, :-1]], axis=1)
        k5 = jnp.concatenate([kp, k5], axis=3)        # (B,n,H,2w,D)
        v5 = jnp.concatenate([vp, v5], axis=3)
    wk = k5.shape[3]

    k_pos = jnp.arange(wk)[None, :]
    if sliding:
        q_pos = jnp.arange(w)[:, None] + w            # within the 2w frame
        valid = (k_pos <= q_pos) & (q_pos - k_pos < w)       # (w, 2w)
        nz = jnp.arange(n) > 0                               # chunk 0: no prev
        mask_n = valid[None] & (nz[:, None, None] | (k_pos >= w)[None])  # (n,w,wk)
    else:
        q_pos = jnp.arange(w)[:, None]
        mask_n = jnp.broadcast_to((k_pos <= q_pos)[None], (n, w, wk))

    if H % tp == 0:
        spec = ("dp", None, "model", None, None)
        q5 = shard(q5, *spec)
        k5 = shard(k5, *spec)
        v5 = shard(v5, *spec)
        s = _scores("bnhqd,bnhkd->bnhqk", q5 * (D ** -0.5), k5)
        s = jnp.where(mask_n[None, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o5 = jnp.einsum("bnhqk,bnhkd->bnhqd", p.astype(v5.dtype), v5)
        o5 = shard(o5, *spec)
    elif G % tp == 0:
        gspec = ("dp", "model", None, None)
        qg = shard(q5.reshape(B, G, w, D), *gspec)
        kg = shard(k5.reshape(B, G, wk, D), *gspec)
        vg = shard(v5.reshape(B, G, wk, D), *gspec)
        mask_g = jnp.repeat(mask_n, H, axis=0)        # (G,w,wk) n-major like G
        s = _scores("bgqd,bgkd->bgqk", qg * (D ** -0.5), kg)
        s = jnp.where(mask_g[None], s, NEG_INF)
        s = shard(s, *gspec)
        p = jax.nn.softmax(s, axis=-1)
        og = jnp.einsum("bgqk,bgkd->bgqd", p.astype(vg.dtype), vg)
        o5 = shard(og, *gspec).reshape(B, n, H, w, D)
    else:
        s = _scores("bnhqd,bnhkd->bnhqk", q5 * (D ** -0.5), k5)
        s = jnp.where(mask_n[None, :, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o5 = jnp.einsum("bnhqk,bnhkd->bnhqd", p.astype(v5.dtype), v5)

    return o5.transpose(0, 1, 3, 2, 4).reshape(B, S, H, D)


def triangular_attention(q, k, v, *, block_q: int = 1024,
                         block_kv: int = 1024, shard_hint=None):
    """Exact causal attention with a Python-unrolled q-block loop so each q
    block only scans its KV prefix -- no masked-out FLOPs beyond the
    diagonal block (the compute-optimal global-attention path; §Perf)."""
    B, Sq, H, D = q.shape
    bq = min(block_q, Sq)
    assert Sq % bq == 0 and q.shape[1] == k.shape[1]
    outs = []
    for i in range(Sq // bq):
        qi = q[:, i * bq:(i + 1) * bq]
        hi = (i + 1) * bq
        outs.append(flash_attention(
            qi, k[:, :hi], v[:, :hi], causal=True, q_offset=i * bq,
            block_kv=min(block_kv, hi), shard_hint=shard_hint))
    return jnp.concatenate(outs, axis=1)


def local_attention(q, k, v, window: int):
    """Exact sliding-window causal attention via the 2-chunk trick.
    q/k/v: (B,S,H,D) flat heads; requires S % window == 0 (else fallback)."""
    S = q.shape[1]
    if window >= S or S % window != 0:
        return flash_attention(q, k, v, causal=True, block_kv=min(1024, S),
                               window=window if window < S else 0)
    return _grouped_windowed(q, k, v, window, sliding=True)


def chunked_attention(q, k, v, chunk: int):
    """llama4-style: causal attention restricted to the query's own chunk."""
    S = q.shape[1]
    if chunk >= S or S % chunk != 0:
        return flash_attention(q, k, v, causal=True, block_kv=min(1024, S),
                               chunk=chunk if chunk < S else 0)
    return _grouped_windowed(q, k, v, chunk, sliding=False)


# --------------------------------------------------------------------------- #
# Decode (single step against a grouped cache)
# --------------------------------------------------------------------------- #
def decode_attention(q, ck, cv, valid_mask, cfg: ArchConfig):
    """q: (B,1,Hq,D) flat; ck/cv: (B,S,Hkv,D); valid_mask: (B,S) or (S,)."""
    B = q.shape[0]
    D = q.shape[-1]
    g = cfg.num_heads // cfg.num_kv_heads
    qg = q.reshape(B, 1, cfg.num_kv_heads, g, D)
    s = _scores("bqhgd,bkhd->bhgqk", qg * (D ** -0.5), ck)
    if valid_mask.ndim == 1:
        valid_mask = valid_mask[None]
    s = jnp.where(valid_mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(cv.dtype), cv)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(B, 1, cfg.num_heads, D)


def sharded_flash_decode(q, ck, cv, pos, cfg: ArchConfig, *, tp_axis="model"):
    """Flash-decode with the cache sequence dim sharded over the TP axis.

    Each shard computes a partial softmax over its sequence slice; partials
    are merged with the (max, sum) trick via pmax/psum. q is replicated over
    the TP axis; ck/cv are P(dp, tp) on (batch, seq)."""
    mesh = current_mesh()
    if mesh is None or tp_axis not in mesh.axis_names:
        S = ck.shape[1]
        return decode_attention(q, ck, cv, jnp.arange(S) <= pos, cfg)
    B, _, Hq, D = q.shape
    # batch too small to shard over the data axes -> replicate batch
    dp = dp_axes()
    if B % max(1, axis_size(dp)) != 0:
        dp = ()
    n_shards = dict(zip(mesh.axis_names, mesh.devices.shape))[tp_axis]
    S_local = ck.shape[1] // n_shards
    g = cfg.num_heads // cfg.num_kv_heads

    def f(q, ck, cv, pos):
        off = jax.lax.axis_index(tp_axis) * S_local
        qg = q.reshape(q.shape[0], 1, cfg.num_kv_heads, g, D)
        s = _scores("bqhgd,bkhd->bhgqk", qg * (D ** -0.5), ck)
        valid = (jnp.arange(S_local) + off) <= pos
        s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
        m = s.max(axis=-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(cv.dtype), cv).astype(jnp.float32)
        M = jax.lax.pmax(m, tp_axis)
        corr = jnp.exp(m - M)
        L = jax.lax.psum(l * corr, tp_axis)
        O = jax.lax.psum(o * corr[..., None], tp_axis)
        out = O / jnp.maximum(L, 1e-20)[..., None]
        out = jnp.transpose(out, (0, 3, 1, 2, 4))
        return out.reshape(out.shape[0], 1, cfg.num_heads, D).astype(cv.dtype)

    from repro.parallel.collectives import shard_map_compat
    return shard_map_compat(
        f, mesh,
        (P(dp), P(dp, tp_axis), P(dp, tp_axis), P()),
        P(dp),
    )(q, ck, cv, pos)


# --------------------------------------------------------------------------- #
# Full mixer (pre-normed input -> attn output), train/prefill/decode
# --------------------------------------------------------------------------- #
def rope_base_for(cfg: ArchConfig, kind: str) -> float:
    if kind == GLOBAL_ATTN and cfg.rope_base_global:
        return cfg.rope_base_global
    return cfg.rope_base


def attn_mixer(params, x, *, cfg: ArchConfig, pcfg: ParallelConfig, kind: str,
               positions=None, cache=None, pos=None, enc_kv=None,
               mode: str = "train"):
    """Returns (out (B,S,D), new_cache_or_None). Cache layout:
      global : {"k","v"}: (B, S_max, Hkv, Dh), abs position p at slot p
      local/chunked : ring buffer (B, W, Hkv, Dh), slot = p mod W
      cross  : read-only {"k","v"} precomputed from encoder output
    """
    B, S, _ = x.shape
    base = rope_base_for(cfg, kind)
    if pcfg.residual_seq_shard and mode != "decode":
        x = shard(x, "dp", None, None)        # gather SP residual for QKV
    q = _project_q(params, x, cfg)

    if kind == "cross":
        k, v = enc_kv
        q = _shard_flat(q, cfg, None)
        o = flash_attention(q, _repeat_kv(k.astype(q.dtype), cfg),
                            _repeat_kv(v.astype(q.dtype), cfg), causal=False,
                            block_kv=min(pcfg.attn_block_kv, k.shape[1]),
                            shard_hint="heads" if _head_tp(cfg) else "seq")
        return _out_proj(params, o, cfg), None

    if mode == "decode":
        # pos is either a scalar (all rows at the same position -- single
        # session) or a (B,) vector of per-slot positions (continuous
        # batching: each request slot decodes at its own offset).
        vec = getattr(pos, "ndim", 0) == 1
        p2 = pos[:, None] if vec else pos + jnp.zeros((B, 1), jnp.int32)
        q = apply_rope(q, p2, base)
        k, v = _project_kv(params, x, cfg)
        k = apply_rope(k, p2, base)
        if kind == GLOBAL_ATTN:
            S_max = cache["k"].shape[1]
            if vec:
                rows = jnp.arange(B)
                ck = cache["k"].at[rows, pos % S_max].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[rows, pos % S_max].set(
                    v[:, 0].astype(cache["v"].dtype))
                o = decode_attention(
                    q, ck, cv, jnp.arange(S_max)[None, :] <= pos[:, None], cfg)
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, pos % S_max, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, pos % S_max, 0, 0))
                if pcfg.decode_seq_shard:
                    o = sharded_flash_decode(q, ck, cv, pos, cfg,
                                             tp_axis=pcfg.tp_axis)
                else:
                    o = decode_attention(q, ck, cv, jnp.arange(S_max) <= pos, cfg)
        else:  # local / chunked ring buffer
            W = cache["k"].shape[1]
            slot = pos % W
            if vec:
                rows = jnp.arange(B)
                ck = cache["k"].at[rows, slot].set(k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[rows, slot].set(v[:, 0].astype(cache["v"].dtype))
                idx = jnp.arange(W)[None, :]
                slot_b, pos_b = slot[:, None], pos[:, None]
            else:
                ck = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
                idx = jnp.arange(W)
                slot_b, pos_b = slot, pos
            abs_pos = pos_b - ((slot_b - idx) % W)    # position held in slot i
            if kind == LOCAL_ATTN:
                valid = (abs_pos >= 0) & (abs_pos > pos_b - W) & (abs_pos <= pos_b)
            else:  # chunked: same chunk as pos
                valid = (abs_pos >= 0) & (abs_pos // W == pos_b // W) \
                    & (abs_pos <= pos_b)
            o = decode_attention(q, ck, cv, valid, cfg)
        return _out_proj(params, o, cfg), {"k": ck, "v": cv}

    # train / prefill
    if positions is None:
        positions = jnp.arange(S)[None, :]
    head_tp = _head_tp(cfg)
    windowed = kind in (LOCAL_ATTN, CHUNKED_ATTN) and cfg.window < S \
        and S % cfg.window == 0

    # Pin shardings BEFORE rope so its fp32 internals never cross shards.
    if not windowed:
        q = _shard_flat(q, cfg, None)
    elif head_tp:
        q = shard(q, "dp", None, "model", None)
    q = apply_rope(q, positions, base)
    tap("attn.q_rot", q)
    k, v = _project_kv(params, x, cfg)
    if not windowed or head_tp:
        if head_tp and cfg.num_kv_heads % axis_size("model") == 0:
            k = shard(k, "dp", None, "model", None)
            v = shard(v, "dp", None, "model", None)
        else:
            # KV is small under GQA: gather it (replicate over model) so
            # scores never contract a sharded head_dim.
            k = shard(k, "dp", None, None, None)
            v = shard(v, "dp", None, None, None)
    k = apply_rope(k, positions, base)
    kf, vf = _repeat_kv(k, cfg), _repeat_kv(v, cfg)
    if head_tp:
        kf = shard(kf, "dp", None, "model", None)
        vf = shard(vf, "dp", None, "model", None)
    hint = "heads" if head_tp else "seq"

    if kind == LOCAL_ATTN:
        o = local_attention(q, kf, vf, cfg.window)
    elif kind == CHUNKED_ATTN:
        o = chunked_attention(q, kf, vf, cfg.window)
    elif kind == BIDIR_ATTN:
        o = flash_attention(q, kf, vf, causal=False,
                            block_kv=min(pcfg.attn_block_kv, S), shard_hint=hint)
    else:
        o = flash_attention(q, kf, vf, causal=True,
                            block_kv=min(pcfg.attn_block_kv, S), shard_hint=hint)

    new_cache = None
    if mode == "prefill":
        # caches keep the compute dtype; serving casts to the serving cache
        # dtype (bf16) when splicing into the generation cache
        if kind in (GLOBAL_ATTN, BIDIR_ATTN):
            new_cache = {"k": k, "v": v}
        else:
            W = min(cfg.window, S)
            new_cache = {"k": k[:, -W:], "v": v[:, -W:]}
    return _out_proj(params, o, cfg), new_cache


def attn_cache_schema(cfg: ArchConfig, kind: str, batch: int, s_max: int,
                      dtype=jnp.bfloat16, *, seq_shard: bool = False):
    """Abstract cache spec for one attention layer (used by launch/serve)."""
    if kind == GLOBAL_ATTN:
        size = s_max
        seq_axis = "model" if seq_shard else None
    else:
        size = min(cfg.window, s_max)
        seq_axis = None
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    spec = P(("pod", "data"), seq_axis, None, None)
    return {"k": (shape, dtype, spec), "v": (shape, dtype, spec)}
