"""Model-level invariants:
  * blockwise/grouped attention variants == naive masked softmax reference
  * prefill + decode == full forward (cache consistency), per layer family
  * chunked cross-entropy == unchunked
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.configs.base import ParallelConfig
from repro.models import attention as A
from repro.models import model as M
from repro.runtime import steps as S

PCFG = ParallelConfig(attn_block_kv=32, xent_chunk=16, scan_chunk=16)


def naive_attention(q, k, v, *, causal, window=0, chunk=0):
    B, Sq, H, D = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    qi = jnp.arange(Sq)[:, None]
    ki = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= ki <= qi
    if window:
        mask &= (qi - ki) < window
    if chunk:
        mask &= (qi // chunk) == (ki // chunk)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v)
    return jnp.transpose(o, (0, 2, 1, 3))


@settings(max_examples=12, deadline=None)
@given(seed=st.integers(0, 50), s_len=st.sampled_from([64, 128]),
       h=st.sampled_from([1, 2, 4]))
def test_flash_matches_naive(seed, s_len, h):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (2, s_len, h, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s_len, h, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s_len, h, 16))
    out = A.flash_attention(q, k, v, causal=True, block_kv=32)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("window", [16, 32])
def test_local_matches_naive(window):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, 128, 3, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 3, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 3, 16))
    out = A.local_attention(q, k, v, window)
    ref = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("chunk", [32, 64])
def test_chunked_matches_naive(chunk):
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (2, 128, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 128, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 128, 2, 16))
    out = A.chunked_attention(q, k, v, chunk)
    ref = naive_attention(q, k, v, causal=True, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def _dots(jaxpr):
    """Every dot_general of a jaxpr, sub-jaxprs (scan, checkpoint, jit)
    included, in order."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_dots(inner))
    return out


@pytest.mark.parametrize("variant", ["flash", "local", "decode"])
def test_attention_scores_are_f32_from_bf16(variant):
    """Served bf16 q and k give f32 scores, produced once: a bf16 score
    that the running max reads rounded and the exponent reads unrounded
    (XLA may drop the rounding in one fusion) made exp(s - max) overflow
    at a score spread of 6e4 on a TPU v5e, and served non-finite logits."""
    q, k, v = (jnp.ones((2, 32, 2, 16), jnp.bfloat16) for _ in range(3))
    cfg = dataclasses.replace(reduced(get_config("gemma3-1b")), num_heads=2,
                              num_kv_heads=2, head_dim=16)
    fn = {"flash": lambda q, k, v: A.flash_attention(q, k, v, causal=True,
                                                      block_kv=8),
          "local": lambda q, k, v: A.local_attention(q, k, v, 16),
          "decode": lambda q, k, v: A.decode_attention(
              q[:, -1:], k, v, jnp.arange(32) <= 31, cfg)}[variant]
    score = _dots(jax.make_jaxpr(fn)(q, k, v).jaxpr)[0]
    assert [a.aval.dtype for a in score.invars] == [jnp.bfloat16] * 2
    assert score.outvars[0].aval.dtype == jnp.float32


def test_triangular_matches_flash():
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (1, 256, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 16))
    out = A.triangular_attention(q, k, v, block_q=64, block_kv=64)
    ref = A.flash_attention(q, k, v, causal=True, block_kv=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


# --------------------------------------------------------------------------- #
# prefill + decode == full forward
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("arch", ["gemma3-1b", "falcon-mamba-7b",
                                  "recurrentgemma-2b", "llama4-scout-17b-a16e",
                                  "seamless-m4t-large-v2"])
def test_decode_consistency(arch):
    """logits(prefill S, decode S..S+2) == logits(full forward S+3)."""
    cfg = reduced(get_config(arch))
    B, P, G = 2, 32, 3
    total = P + G
    key = jax.random.PRNGKey(0)
    toks = jax.random.randint(key, (B, total), 0, cfg.vocab_size)
    params = S.init_train_state(key, cfg)["params"]

    extra = {}
    if cfg.frontend == "vision":
        extra["image_embeds"] = jax.random.normal(
            key, (B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    if cfg.encoder_layers:
        extra["enc_frames"] = jax.random.normal(
            key, (B, P, cfg.d_model), jnp.float32)

    # full forward on all tokens (eval mode: same MoE routing as decode)
    h_full, _, _ = M.forward(params, toks, cfg=cfg, pcfg=PCFG, mode="prefill",
                             compute_dtype=jnp.float32, **extra)
    logits_full = M.compute_logits(params, h_full, cfg)

    # prefill P tokens, then decode G tokens (teacher forcing)
    h_pre, cache, _ = M.forward(params, toks[:, :P], cfg=cfg, pcfg=PCFG,
                                mode="prefill", compute_dtype=jnp.float32,
                                **extra)
    logits_pre = M.compute_logits(params, h_pre, cfg)
    np.testing.assert_allclose(np.asarray(logits_pre[:, -1]),
                               np.asarray(logits_full[:, P - 1]),
                               rtol=3e-3, atol=3e-3)

    # pad attention caches from P to `total` positions where needed
    cs = M.model_cache_schema(cfg, B, total, dtype=jnp.float32,
                              cross_len=(P if cfg.encoder_layers else 0))
    zero = M.zeros_cache(cs)

    def splice(z, c):
        c = c.astype(z.dtype)
        if z.shape == c.shape:
            return c
        pads = [(0, zd - cd) for zd, cd in zip(z.shape, c.shape)]
        return jnp.pad(c, pads)

    cache = jax.tree.map(splice, zero, cache)
    for i in range(G):
        pos = jnp.asarray(P + i, jnp.int32)
        logits_dec, cache = M.decode_step(params, toks[:, P + i:P + i + 1],
                                          cache, pos, cfg=cfg, pcfg=PCFG,
                                          compute_dtype=jnp.float32)
        np.testing.assert_allclose(
            np.asarray(logits_dec), np.asarray(logits_full[:, P + i]),
            rtol=3e-3, atol=3e-3,
            err_msg=f"{arch} decode step {i}")


# --------------------------------------------------------------------------- #
# chunked xent == full xent
# --------------------------------------------------------------------------- #
def test_chunked_xent_matches_full():
    cfg = reduced(get_config("deepseek-coder-33b"))
    key = jax.random.PRNGKey(0)
    B, S_len = 2, 64
    params = S.init_train_state(key, cfg)["params"]
    h = jax.random.normal(key, (B, S_len, cfg.d_model)) * 0.3
    t = jax.random.randint(jax.random.fold_in(key, 1), (B, S_len), 0,
                           cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.fold_in(key, 2), (B, S_len)) > 0.2
            ).astype(jnp.float32)
    chunked = M.chunked_xent(params, h, t, mask, cfg=cfg, chunk=16, z_coef=0.0)
    full = M.chunked_xent(params, h, t, mask, cfg=cfg, chunk=S_len, z_coef=0.0)
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-5)
