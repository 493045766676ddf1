"""Step-function builders: train_step / prefill_step / decode_step, plus the
abstract state/batch trees (ShapeDtypeStruct + NamedSharding) used both by
the dry-run (AOT lowering, zero allocation) and the real trainer.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import (ArchConfig, ParallelConfig, ShapeConfig,
                                TrainConfig)
from repro.models import model as M
from repro.models.common import (abstract_params, abstract_array, init_params,
                                 use_mesh, dp_axes)
from repro.optim.adamw import adamw_update, init_opt_schema, global_norm


def compute_dtype_of(pcfg: ParallelConfig):
    return jnp.bfloat16 if pcfg.compute_dtype == "bfloat16" else jnp.float32


# --------------------------------------------------------------------------- #
# State schemas
# --------------------------------------------------------------------------- #
def train_state_schema(cfg: ArchConfig):
    ps = M.model_schema(cfg)
    return {"params": ps, "opt": init_opt_schema(ps)}


def abstract_train_state(cfg: ArchConfig, mesh: Optional[Mesh]):
    sch = train_state_schema(cfg)
    state = {
        "params": abstract_params(sch["params"], mesh),
        "opt": abstract_params(sch["opt"], mesh),
        "step": abstract_array((), jnp.int32, P(), mesh),
    }
    return state


def init_train_state(key, cfg: ArchConfig):
    sch = train_state_schema(cfg)
    return {
        "params": init_params(key, sch["params"]),
        "opt": init_params(key, sch["opt"]),
        "step": jnp.zeros((), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# Batch specs
# --------------------------------------------------------------------------- #
def abstract_params_bf16(cfg: ArchConfig, mesh: Optional[Mesh]):
    """Serving-time parameter tree (bf16)."""
    return abstract_params(M.model_schema(cfg), mesh, dtype=jnp.bfloat16)


def train_batch_abstract(cfg: ArchConfig, shape: ShapeConfig,
                         mesh: Optional[Mesh]):
    B, S = shape.global_batch, shape.seq_len
    dp = ("pod", "data")
    b: Dict[str, Any] = {
        "tokens": abstract_array((B, S), jnp.int32, P(dp, None), mesh),
        "targets": abstract_array((B, S), jnp.int32, P(dp, None), mesh),
        "mask": abstract_array((B, S), jnp.float32, P(dp, None), mesh),
    }
    if cfg.frontend == "vision":
        b["image_embeds"] = abstract_array(
            (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16,
            P(dp, None, None), mesh)
    if cfg.encoder_layers:
        b["enc_frames"] = abstract_array(
            (B, S, cfg.d_model), jnp.bfloat16, P(dp, None, None), mesh)
    return b


# --------------------------------------------------------------------------- #
# Train step
# --------------------------------------------------------------------------- #
def make_train_step(cfg: ArchConfig, pcfg: ParallelConfig, tcfg: TrainConfig):
    cdt = compute_dtype_of(pcfg)

    def loss_of(params, batch):
        # cast matrices to the compute dtype ONCE per step, before any use:
        # FSDP weight all-gathers then move bf16 (2x fewer bytes) instead of
        # f32 master weights; grads still flow to the f32 masters
        params = jax.tree.map(
            lambda p: p.astype(cdt)
            if (p.ndim >= 2 and p.dtype == jnp.float32) else p, params)
        return M.lm_loss(params, batch, cfg=cfg, pcfg=pcfg,
                         compute_dtype=cdt, z_coef=tcfg.z_loss)

    def train_step(state, batch):
        m = max(1, pcfg.grad_accum)
        if m == 1:
            (loss, parts), grads = jax.value_and_grad(loss_of, has_aux=True)(
                state["params"], batch)
        else:
            # microbatched gradient accumulation: only one microbatch's remat
            # stash is live at a time; grads accumulate in (sharded) fp32
            mb = jax.tree.map(
                lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), batch)

            def one(carry, b):
                gacc, lacc, xacc, aacc = carry
                (l, p), g = jax.value_and_grad(loss_of, has_aux=True)(
                    state["params"], b)
                gacc = jax.tree.map(
                    lambda a, x: a + x.astype(jnp.float32), gacc, g)
                return (gacc, lacc + l, xacc + p["xent"], aacc + p["aux"]), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (gsum, lsum, xsum, asum), _ = jax.lax.scan(
                one, (zeros, jnp.zeros((), jnp.float32),
                      jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                mb)
            grads = jax.tree.map(lambda g: g / m, gsum)
            loss = lsum / m
            parts = {"xent": xsum / m, "aux": asum / m}

        new_params, new_opt, om = adamw_update(
            state["params"], grads, state["opt"], state["step"], tcfg)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = {"loss": loss, **parts, **om}
        return new_state, metrics

    return train_step


# --------------------------------------------------------------------------- #
# Serving steps
# --------------------------------------------------------------------------- #
def make_prefill_step(cfg: ArchConfig, pcfg: ParallelConfig):
    cdt = compute_dtype_of(pcfg)

    def prefill_step(params, batch, all_positions=False, taps=False):
        return M.prefill(params, batch["tokens"], cfg=cfg, pcfg=pcfg,
                         image_embeds=batch.get("image_embeds"),
                         enc_frames=batch.get("enc_frames"),
                         compute_dtype=cdt, all_positions=all_positions,
                         taps=taps)

    return prefill_step


def make_decode_step(cfg: ArchConfig, pcfg: ParallelConfig):
    cdt = compute_dtype_of(pcfg)

    def decode_step(params, token, cache, pos):
        return M.decode_step(params, token, cache, pos, cfg=cfg, pcfg=pcfg,
                             compute_dtype=cdt)

    return decode_step


def prefill_batch_abstract(cfg: ArchConfig, shape: ShapeConfig,
                           mesh: Optional[Mesh]):
    B, S = shape.global_batch, shape.seq_len
    dp = ("pod", "data")
    b: Dict[str, Any] = {
        "tokens": abstract_array((B, S), jnp.int32, P(dp, None), mesh),
    }
    if cfg.frontend == "vision":
        b["image_embeds"] = abstract_array(
            (B, cfg.frontend_tokens, cfg.d_model), jnp.bfloat16,
            P(dp, None, None), mesh)
    if cfg.encoder_layers:
        b["enc_frames"] = abstract_array(
            (B, S, cfg.d_model), jnp.bfloat16, P(dp, None, None), mesh)
        b["tokens"] = abstract_array((B, max(S // 32, 8)), jnp.int32,
                                     P(dp, None), mesh)
    return b


def decode_inputs_abstract(cfg: ArchConfig, shape: ShapeConfig,
                           mesh: Optional[Mesh], pcfg: ParallelConfig):
    """(params_bf16, token, cache, pos) abstract trees for one decode step."""
    B, S = shape.global_batch, shape.seq_len
    dp = ("pod", "data")
    params = abstract_params(M.model_schema(cfg), mesh, dtype=jnp.bfloat16)
    token = abstract_array((B, 1), jnp.int32, P(dp, None), mesh)
    pos = abstract_array((), jnp.int32, P(), mesh)
    cs = M.model_cache_schema(
        cfg, B, S, seq_shard=pcfg.decode_seq_shard,
        cross_len=(S if cfg.encoder_layers else 0))
    cache = M.abstract_cache(cs, mesh)
    return params, token, cache, pos
