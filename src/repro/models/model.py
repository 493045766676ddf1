"""Full model: embeddings -> (encoder) -> period-scanned decoder stack ->
final norm -> LM head, with train / prefill / decode entry points and a
chunked cross-entropy loss (no B x S x V materialization).

Layers are grouped into the arch's repeating ``pattern`` period; the period
body is Python-unrolled (heterogeneous sub-layers), ``lax.scan`` runs over
periods with stacked params, remainder layers are unrolled at the tail.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ArchConfig, ParallelConfig, BIDIR_ATTN)
from repro.models.blocks import (apply_layer, layer_schema, layer_cache_schema)
from repro.models.common import (ParamSchema, abstract_array, apply_norm,
                                 current_mesh, dense, norm_schema,
                                 scan_states_provider, shard, stack_schema,
                                 tap, tapping, tapping_on, _sanitize_spec)

NEG_INF = -1e30


# --------------------------------------------------------------------------- #
# Schema
# --------------------------------------------------------------------------- #
def model_schema(cfg: ArchConfig) -> Dict[str, Any]:
    d, vp = cfg.d_model, cfg.padded_vocab
    cross = cfg.encoder_layers > 0
    s: Dict[str, Any] = {
        "embed": ParamSchema((vp, d), P("model", "data"), "embed", d ** -0.5),
        "final_norm": norm_schema(d, cfg.norm),
    }
    if not cfg.tie_embeddings:
        s["head"] = ParamSchema((d, vp), P("data", "model"), "normal", d ** -0.5)
    if cfg.head_bias:
        s["head_bias"] = ParamSchema((vp,), P("model"), "zeros")
    if cfg.frontend == "vision":
        s["proj"] = ParamSchema((d, d), P("data", "model"), "normal", d ** -0.5)

    scan: Dict[str, Any] = {}
    if cfg.num_periods > 0:
        for i, kind in enumerate(cfg.pattern):
            scan[f"p{i}"] = stack_schema(layer_schema(cfg, kind, cross=cross),
                                         cfg.num_periods)
    tail = {f"t{i}": layer_schema(cfg, kind, cross=cross)
            for i, kind in enumerate(cfg.tail_kinds)}
    s["decoder"] = {"scan": scan, "tail": tail}

    if cross:
        enc_scan = {"p0": stack_schema(layer_schema(cfg, BIDIR_ATTN),
                                       cfg.encoder_layers)}
        s["encoder"] = {"scan": enc_scan, "tail": {},
                        "final_norm": norm_schema(d, cfg.norm)}
    return s


def model_cache_schema(cfg: ArchConfig, batch: int, s_max: int, *,
                       seq_shard: bool = False, cross_len: int = 0,
                       dtype=None):
    """{scan: {p_i: stacked-layer cache schema}, tail: {...}} of
    (shape, dtype, PartitionSpec) leaves."""
    def stack_leaf(leaf, n):
        shape, dtype, spec = leaf
        return ((n,) + tuple(shape), dtype, P(None, *spec))

    def is_leaf(x):
        return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)

    scan = {}
    if cfg.num_periods > 0:
        for i, kind in enumerate(cfg.pattern):
            ls = layer_cache_schema(cfg, kind, batch, s_max,
                                    cross_len=cross_len, seq_shard=seq_shard,
                                    dtype=dtype)
            scan[f"p{i}"] = jax.tree.map(
                lambda l: stack_leaf(l, cfg.num_periods), ls, is_leaf=is_leaf)
    tail = {f"t{i}": layer_cache_schema(cfg, kind, batch, s_max,
                                        cross_len=cross_len,
                                        seq_shard=seq_shard, dtype=dtype)
            for i, kind in enumerate(cfg.tail_kinds)}
    return {"scan": scan, "tail": tail}


def _cache_is_leaf(x):
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)


def abstract_cache(cache_schema, mesh=None):
    return jax.tree.map(
        lambda l: abstract_array(l[0], l[1], l[2], mesh),
        cache_schema, is_leaf=_cache_is_leaf)


def zeros_cache(cache_schema):
    return jax.tree.map(lambda l: jnp.zeros(l[0], l[1]),
                        cache_schema, is_leaf=_cache_is_leaf)


# --------------------------------------------------------------------------- #
# Stack runner
# --------------------------------------------------------------------------- #
def _remat_wrap(fn, pcfg: ParallelConfig):
    if pcfg.remat == "none":
        return fn
    if pcfg.remat == "dots":
        pol = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


def _period_slice(v, p: int):
    """Period ``p`` of a stacked parameter while call sites are recorded.
    The params are concrete (closed over): a period's 2-D weights -- the
    only kind dense() takes -- are sliced OUT of the ambient trace, so
    dense() records real arrays, not tracers that would leak out of the
    eval_shape scope; larger banks (a MoE layer's experts) are sliced in
    the trace, so no concrete copy of them is made."""
    if v.ndim <= 3:
        with jax.ensure_compile_time_eval():
            return v[p]
    return v[p]


def _run_stack(stack_params, x, *, cfg: ArchConfig, pcfg: ParallelConfig,
               pattern, tail_kinds, mode, caches, pos, positions, enc_out,
               scan_group: str = "dec"):
    """Runs scan-over-periods + unrolled tail. Returns (x, aux, new_caches).

    When a scan-states provider is installed (``models.common.
    use_scan_states``; a serving session threading per-site analog
    ``DeploymentState``s), the scanned periods cooperate with it: in
    record mode the period loop is Python-unrolled so every ``dense()``
    call site sees its CONCRETE per-period weight slice (call sites keyed
    ``"{scan_group}.{period}:{tag}#{ordinal}"``); in serve mode the scan
    carries the period index and the provider picks that period's traced
    states, so each period's sites resolve against them and the whole
    stack stays ONE compiled step -- scanned models get the same
    zero-recompile state swaps as unrolled ones."""
    provider = scan_states_provider()

    def period_fn(x, aux, lp, lc, ls=None):
        # The scan carry is saved per period by remat: keep it SEQ-SHARDED
        # over the model axis so the stash is L/period x (B,S/tp,D) per
        # device (Megatron-SP-style); gather once per period for compute.
        ctx = (provider.scan_slice(scan_group, ls)
               if provider is not None and ls is not None
               else contextlib.nullcontext())
        with ctx:
            if not pcfg.residual_seq_shard:
                x = shard(x, "dp", None, None)
            ncs = {}
            for i, kind in enumerate(pattern):
                with tapping(tapping_on()) as taps:
                    tap("x_in", x)
                    x, nc, a = apply_layer(
                        lp[f"p{i}"], x, cfg=cfg, pcfg=pcfg, kind=kind,
                        mode=mode,
                        cache=None if lc is None else lc.get(f"p{i}"),
                        pos=pos, positions=positions, enc_out=enc_out)
                if taps is not None:
                    nc = dict(nc or {}, taps=taps)
                if nc is not None:
                    ncs[f"p{i}"] = nc
                aux = aux + a
            x = shard(x, "dp", "model", None)
        return x, aux, (ncs if ncs else None)

    period = _remat_wrap(period_fn, pcfg)
    aux = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {"scan": {}, "tail": {}}

    scan_params = stack_params["scan"]
    if scan_params:
        n = jax.tree.leaves(scan_params)[0].shape[0]
        if provider is not None and provider.recording:
            # call-site discovery: unroll the periods so dense() records
            # concrete weight slices under stable per-period site keys
            # (runs under eval_shape -- activations are abstract, the
            # closed-over params and their slices are concrete)
            ncs = []
            for p in range(n):
                lp = jax.tree.map(lambda v: _period_slice(v, p), scan_params)
                lc = (jax.tree.map(lambda v: v[p], caches["scan"])
                      if mode == "decode" else None)
                with provider.scan_record(scan_group, p):
                    x, aux, nc = period_fn(x, aux, lp, lc)
                ncs.append(nc)
            if mode in ("prefill", "decode") and ncs[0] is not None:
                new_caches["scan"] = jax.tree.map(
                    lambda *vs: jnp.stack(vs), *ncs)
        else:
            pick = (provider.scan_pick(scan_group, n)
                    if provider is not None else None)
            # the period index, not stacked states, rides the scan: a
            # stacked copy of every period's states would double their
            # device footprint for the length of the step
            periods = jnp.arange(n) if pick is not None else None

            def states_at(i):
                return None if pick is None else pick(i)

            if mode == "decode":
                def body(carry, xs):
                    lp, lc, i = xs
                    x, aux = carry
                    x, aux, nc = period(x, aux, lp, lc, states_at(i))
                    return (x, aux), nc
                (x, aux), ys = jax.lax.scan(
                    body, (x, aux), (scan_params, caches["scan"], periods))
                new_caches["scan"] = ys
            elif mode == "prefill":
                def body(carry, xs):
                    lp, i = xs
                    x, aux = carry
                    x, aux, nc = period(x, aux, lp, None, states_at(i))
                    return (x, aux), nc
                (x, aux), ys = jax.lax.scan(body, (x, aux),
                                            (scan_params, periods))
                new_caches["scan"] = ys
            else:
                def body(carry, xs):
                    lp, i = xs
                    x, aux = carry
                    x, aux, _ = period(x, aux, lp, None, states_at(i))
                    return (x, aux), None
                (x, aux), _ = jax.lax.scan(body, (x, aux),
                                           (scan_params, periods))

    for i, kind in enumerate(tail_kinds):
        lc = None
        if mode == "decode":
            lc = caches["tail"].get(f"t{i}")
        with tapping(tapping_on()) as taps:
            tap("x_in", x)
            x, nc, a = apply_layer(
                stack_params["tail"][f"t{i}"], x, cfg=cfg, pcfg=pcfg,
                kind=kind, mode=mode, cache=lc, pos=pos, positions=positions,
                enc_out=enc_out)
        if taps is not None:
            nc = dict(nc or {}, taps=taps)
        aux = aux + a
        if nc is not None:
            new_caches["tail"][f"t{i}"] = nc

    return x, aux, new_caches


# --------------------------------------------------------------------------- #
# Forward passes
# --------------------------------------------------------------------------- #
def embed_tokens(params, tokens, cfg: ArchConfig, compute_dtype):
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)
    if cfg.emb_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, compute_dtype)
    return x


def encode(params, enc_frames, *, cfg: ArchConfig, pcfg: ParallelConfig):
    """Encoder over precomputed frontend frames (B, S_enc, D)."""
    x = shard(enc_frames, "dp", None, None)
    x, aux, _ = _run_stack(
        {"scan": params["encoder"]["scan"], "tail": {}}, x, cfg=cfg, pcfg=pcfg,
        pattern=(BIDIR_ATTN,), tail_kinds=(), mode="train", caches=None,
        pos=None, positions=None, enc_out=None, scan_group="enc")
    return apply_norm(params["encoder"]["final_norm"], x, cfg.norm), aux


def _split_taps(caches, cfg: ArchConfig):
    """(a prefill's caches without the layers' taps, each layer's taps in
    depth order)."""
    n_pat = len(cfg.pattern)
    found = []                                   # (layer, taps)
    out = {"scan": {}, "tail": {}}
    for key, c in caches["scan"].items():
        c = dict(c)
        t = c.pop("taps")
        if c:
            out["scan"][key] = c
        i = int(key[1:])
        for p in range(jax.tree.leaves(t)[0].shape[0]):
            found.append((p * n_pat + i, jax.tree.map(lambda a, p=p: a[p], t)))
    for key, c in caches["tail"].items():
        c = dict(c)
        found.append((cfg.num_periods * n_pat + int(key[1:]), c.pop("taps")))
        if c:
            out["tail"][key] = c
    found.sort(key=lambda lt: lt[0])
    return out, [t for _, t in found]


def forward(params, tokens, *, cfg: ArchConfig, pcfg: ParallelConfig,
            mode: str = "train", cache=None, pos=None, image_embeds=None,
            enc_frames=None, compute_dtype=jnp.bfloat16):
    """Returns (hidden (B,S,D), new_cache_or_None, aux_loss).  Tapped:
    ``final``, the last layer's output before the final norm."""
    aux = jnp.zeros((), jnp.float32)
    enc_out = None
    if cfg.encoder_layers:
        if mode == "decode":
            enc_out = None                      # decoder reads cross cache
        else:
            assert enc_frames is not None
            enc_out, aux_e = encode(params, enc_frames.astype(compute_dtype),
                                    cfg=cfg, pcfg=pcfg)
            aux = aux + aux_e

    x = embed_tokens(params, tokens, cfg, compute_dtype)
    if cfg.frontend == "vision" and image_embeds is not None:
        img = dense(image_embeds.astype(compute_dtype), params["proj"], "frontend.proj")
        n = img.shape[1]
        x = jnp.concatenate([img, x[:, n:]], axis=1)
    rs = "model" if (pcfg.residual_seq_shard and mode != "decode") else None
    x = shard(x, "dp", rs, None)

    if mode == "decode":
        positions = None
    else:
        positions = jnp.arange(tokens.shape[1])[None, :]

    x, aux_d, new_caches = _run_stack(
        params["decoder"], x, cfg=cfg, pcfg=pcfg, pattern=cfg.pattern,
        tail_kinds=cfg.tail_kinds, mode=mode, caches=cache, pos=pos,
        positions=positions, enc_out=enc_out)
    aux = aux + aux_d
    tap("final", x)
    x = apply_norm(params["final_norm"], x, cfg.norm)
    return x, (new_caches if mode in ("prefill", "decode") else None), aux


# --------------------------------------------------------------------------- #
# Logits & loss
# --------------------------------------------------------------------------- #
def compute_logits(params, h, cfg: ArchConfig):
    """h: (B,S,D) -> logits (B,S,Vp) fp32, padded vocab masked."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h,
                            params["embed"].astype(h.dtype))
    else:
        logits = dense(h, params["head"], "lm_head")
    if "head_bias" in params:
        logits = logits + params["head_bias"].astype(logits.dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = jnp.where(mask[None, None, :], NEG_INF, logits)
    return logits


def chunked_xent(params, h, targets, mask, *, cfg: ArchConfig,
                 chunk: int, z_coef: float = 0.0):
    """Mean xent over masked positions; logits live one seq-chunk at a time."""
    B, S, D = h.shape
    ck = min(chunk, S)
    if S % ck != 0:
        ck = S
    n = S // ck

    def chunk_fn(hc, tc, mc):
        # vocab-sharded logits: lse reduces over the sharded vocab dim (small
        # all-reduce) and the target gather lowers to mask+reduce -- both tiny
        hc = shard(hc, "dp", None, None)
        logits = compute_logits(params, hc, cfg)
        logits = shard(logits, "dp", None, "model")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0] - lse
        zl = z_coef * jnp.square(lse) if z_coef else 0.0
        m = mc.astype(jnp.float32)
        return ((-ll + zl) * m).sum(), m.sum()

    chunk_fn = jax.checkpoint(chunk_fn)

    def body(carry, xs):
        ls, ms = carry
        l, m = chunk_fn(*xs)
        return (ls + l, ms + m), None

    hr = h.reshape(B, n, ck, D).swapaxes(0, 1)
    tr = targets.reshape(B, n, ck).swapaxes(0, 1)
    mr = mask.reshape(B, n, ck).swapaxes(0, 1)
    (loss_sum, denom), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hr, tr, mr))
    return loss_sum / jnp.maximum(denom, 1.0)


def lm_loss(params, batch, *, cfg: ArchConfig, pcfg: ParallelConfig,
            compute_dtype=jnp.bfloat16, z_coef: float = 1e-4):
    """batch: {tokens, targets, mask, [image_embeds], [enc_frames]}."""
    h, _, aux = forward(
        params, batch["tokens"], cfg=cfg, pcfg=pcfg, mode="train",
        image_embeds=batch.get("image_embeds"),
        enc_frames=batch.get("enc_frames"), compute_dtype=compute_dtype)
    loss = chunked_xent(params, h, batch["targets"], batch["mask"],
                        cfg=cfg, chunk=pcfg.xent_chunk, z_coef=z_coef)
    return loss + aux, {"xent": loss, "aux": aux}


# --------------------------------------------------------------------------- #
# Serving entry points
# --------------------------------------------------------------------------- #
def prefill(params, tokens, *, cfg: ArchConfig, pcfg: ParallelConfig,
            image_embeds=None, enc_frames=None, compute_dtype=jnp.bfloat16,
            all_positions: bool = False, taps: bool = False):
    """Returns (logits, cache): the last position's logits (B,Vp) fp32, or
    with ``all_positions`` every position's (B,S,Vp), as an evaluation
    harness scores sequences.  With ``taps``, also what a check of each
    step on its served inputs reads (``models.common.tap``): ``{"layers":
    per layer in depth order its ``x_in``, each crossbar site's drive
    ``<tag>:in`` and output ``<tag>``, ``attn.q_rot`` and a MoE's
    ``moe.*``; ``final``; ``lm_head:in`` and ``lm_head``}``."""
    with tapping(taps) as top:
        h, cache, _ = forward(params, tokens, cfg=cfg, pcfg=pcfg,
                              mode="prefill", image_embeds=image_embeds,
                              enc_frames=enc_frames,
                              compute_dtype=compute_dtype)
        logits = compute_logits(params, h if all_positions else h[:, -1:],
                                cfg)
    if not all_positions:
        logits = logits[:, 0]
    if not taps:
        return logits, cache
    cache, layers = _split_taps(cache, cfg)
    return logits, cache, dict(top, layers=layers)


def decode_step(params, token, cache, pos, *, cfg: ArchConfig,
                pcfg: ParallelConfig, compute_dtype=jnp.bfloat16):
    """token: (B,1) int32; pos: () int32 -- position being written -- or
    (B,) int32 for per-row positions (continuous batching: each request
    slot decodes at its own offset).  Returns (logits (B,Vp), new_cache)."""
    h, new_cache, _ = forward(params, token, cfg=cfg, pcfg=pcfg, mode="decode",
                              cache=cache, pos=pos, compute_dtype=compute_dtype)
    logits = compute_logits(params, h, cfg)[:, 0]
    return logits, new_cache
