"""Mixture-of-Experts FFN with scatter dispatch and expert parallelism
(experts sharded over the `model` axis; token buffers routed by
GSPMD-inserted all-to-alls).

Routing is chosen by the registry entry (``MoEConfig.router``): softmax
over the experts then top-k, renormalized, or Phi-3.5-MoE's sparsemixer
(``sparsemixer``).  Router logits are f32 from the compute-dtype
activation.

Dispatch is the GShard/Switch scheme implemented with scatter/gather
instead of the O(T*E*C) one-hot einsum (which would not fit memory at
T = 1M tokens):
  pos_in_expert = cumsum(onehot(assign)) - 1
  keep          = pos < capacity
  buffer[e, pos] += x_t          (scatter-add over unique slots)
  y_t            = sum_k gate_k * buffer[e_k, pos_k]
Training drops past ``capacity_factor``; inference is dropless: a token's
k experts are distinct, so no expert holds more than T assignments and a
capacity of T keeps every one (the expert GEMMs then compute E*T rows
for T*k assignments; the gauges ``moe_assignments`` and
``moe_dispatch_rows`` report both).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, MoEConfig, ParallelConfig
from repro.models.common import (ParamSchema, activation, dense_schema, shard,
                                 tap)
from repro.obs import OBS


def moe_schema(cfg: ArchConfig):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    s = {
        "router": ParamSchema((d, e), P(None, None), "normal", d ** -0.5),
        "w_up": ParamSchema((e, d, f), P("model", "data", None), "normal", d ** -0.5),
        "w_down": ParamSchema((e, f, d), P("model", None, "data"), "normal", f ** -0.5),
    }
    if cfg.mlp_gated:
        s["w_gate"] = ParamSchema((e, d, f), P("model", "data", None), "normal", d ** -0.5)
    if cfg.moe.shared_expert:
        s["shared_up"] = dense_schema(d, f)
        s["shared_down"] = dense_schema(f, d, fsdp="model", tp="data")
        if cfg.mlp_gated:
            s["shared_gate"] = dense_schema(d, f)
    return s


def _capacity(n_tokens: int, mcfg: MoEConfig, train: bool) -> int:
    """Slots per expert: ``capacity_factor`` in training, every token at
    inference (dropless)."""
    if not train:
        return n_tokens
    c = int(n_tokens * mcfg.top_k * mcfg.capacity_factor / mcfg.num_experts)
    return max(4, -(-c // 4) * 4)


def sparsemixer(logits, top_k: int, eps: float):
    """Phi-3.5-MoE's router at inference (PhiMoE ``sparsemixer``).

    For each choice, s are the router logits with the earlier choices at
    -inf and m = max s: every expert with (m - s_e) / max(|s_e|, m) >
    2 * eps (s_e the unmasked logit) is masked, the survivors are
    softmaxed, the choice is the argmax and its multiplier its softmax
    weight.  The multipliers are not renormalized.

    logits: (T, E) f32.  Returns (multipliers (T, K) f32, experts (T, K)
    int32)."""
    masked = logits
    weights, experts = [], []
    for _ in range(top_k):
        m = masked.max(axis=-1, keepdims=True)
        e = jnp.argmax(masked, axis=-1)
        far = (m - logits) / jnp.maximum(jnp.abs(logits), m) > 2 * eps
        p = jax.nn.softmax(jnp.where(far, -jnp.inf, masked), axis=-1)
        weights.append(jnp.take_along_axis(p, e[:, None], axis=-1)[:, 0])
        experts.append(e)
        masked = jnp.where(jax.nn.one_hot(e, logits.shape[-1], dtype=bool),
                           -jnp.inf, masked)
    return (jnp.stack(weights, axis=-1),
            jnp.stack(experts, axis=-1).astype(jnp.int32))


def _route(logits, mcfg: MoEConfig):
    """(multipliers (T, K) f32, experts (T, K) int32) of the config's
    router."""
    if mcfg.router == "sparsemixer":
        return sparsemixer(logits, mcfg.top_k, mcfg.router_jitter)
    gate_vals, expert_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                          mcfg.top_k)
    if mcfg.top_k > 1:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, expert_idx.astype(jnp.int32)


def moe_mixer(params, x, *, cfg: ArchConfig, pcfg: ParallelConfig,
              train: bool = True) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D). Returns (y (B,S,D), aux_loss scalar fp32).  Tapped:
    ``moe.in`` (x), each token's chosen ``moe.experts`` (B, S, K) int32
    and their ``moe.weights`` (B, S, K) f32, and ``moe.out`` (y)."""
    mcfg = cfg.moe
    if pcfg.residual_seq_shard:
        x = shard(x, "dp", None, None)
    B, S, D = x.shape
    T = B * S
    E, K = mcfg.num_experts, mcfg.top_k
    C = _capacity(T, mcfg, train)
    act = activation(cfg.mlp_act)
    if OBS.enabled:
        # trace-time: one series per traced shape (a scan's periods share
        # one trace); dispatch rows over assignments is the padding
        labels = dict(t=str(T), e=str(E), k=str(K),
                      mode="train" if train else "serve")
        OBS.gauge("moe_assignments", "token-expert assignments a MoE layer "
                  "routes (T * k)", **labels).set(T * K)
        OBS.gauge("moe_dispatch_rows", "rows the expert GEMMs of a MoE "
                  "layer compute (experts x capacity)", **labels).set(E * C)

    tap("moe.in", x)
    xt = x.reshape(T, D)
    with jax.named_scope("moe_route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            params["router"].astype(jnp.float32))
        gate_vals, expert_idx = _route(logits, mcfg)              # (T, K)

    with jax.named_scope("moe_experts"):
        # position of each assignment within its expert (global order); an
        # explicit log-depth associative scan -- jnp.cumsum lowers to an
        # O(n^2) reduce-window on some backends (the HLO cost model says so)
        assign_oh = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (T,K,E)
        flat_oh = assign_oh.reshape(T * K, E)
        csum = jax.lax.associative_scan(jnp.add, flat_oh, axis=0)  # inclusive
        pos = csum - flat_oh                                        # (T*K, E)
        pos = (pos.reshape(T, K, E) * assign_oh).sum(-1)            # (T, K)
        keep = pos < C

        # dropped assignments write (masked-to-zero) into the last slot, so
        # the buffer stays exactly (E*C, D) and shards cleanly over experts
        slot = jnp.where(keep, expert_idx * C + pos, E * C - 1)
        slot = shard(slot.reshape(T * K), "dp")
        xk = jnp.broadcast_to(xt[:, None], (T, K, D)).reshape(T * K, D)
        xk = shard(xk * keep.reshape(-1, 1).astype(xt.dtype), "dp", None)
        buf = jnp.zeros((E * C, D), xt.dtype).at[slot].add(xk)
        buf = shard(buf.reshape(E, C, D), "model", None, None)

        up = jnp.einsum("ecd,edf->ecf", buf,
                        params["w_up"].astype(buf.dtype))
        up = shard(up, "model", None, None)
        if cfg.mlp_gated:
            g = jnp.einsum("ecd,edf->ecf", buf,
                           params["w_gate"].astype(buf.dtype))
            h = act(shard(g, "model", None, None)) * up
        else:
            h = act(up)
        yb = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(h.dtype))
        yb = shard(yb, "model", None, None)

        yk = yb.reshape(E * C, D)[slot].reshape(T, K, D)
        y = (yk * (gate_vals * keep).astype(yk.dtype)[..., None]).sum(axis=1)
        y = shard(y, "dp", None)

        if mcfg.shared_expert:
            up_s = jnp.einsum("td,df->tf", xt,
                              params["shared_up"].astype(xt.dtype))
            if cfg.mlp_gated:
                g_s = jnp.einsum("td,df->tf", xt,
                                 params["shared_gate"].astype(xt.dtype))
                h_s = act(g_s) * up_s
            else:
                h_s = act(up_s)
            y = y + jnp.einsum("tf,fd->td", h_s,
                               params["shared_down"].astype(h_s.dtype))

    # Switch-style load-balance aux loss
    top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    frac_tokens = top1.mean(axis=0)
    frac_probs = jax.nn.softmax(logits, axis=-1).mean(axis=0)
    aux = E * jnp.sum(frac_tokens * frac_probs) * mcfg.router_aux_coef

    y = y.reshape(B, S, D)
    tap("moe.experts", expert_idx.reshape(B, S, K))
    tap("moe.weights", gate_vals.reshape(B, S, K))
    tap("moe.out", y)
    return y, aux
