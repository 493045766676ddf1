"""MoE routing and dispatch at inference:
  * Phi-3.5-MoE's sparsemixer against hand-worked cases (the mask
    threshold, ties, a negative max, multipliers not renormalized);
  * the registry entry chooses the router, softmax-top-k stays the
    default;
  * dropless dispatch: every token routed to one expert is computed, at
    inference, and the gauges report assignments and dispatch rows;
  * ``ServeSession(params=...)`` draws no weights of its own.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import ParallelConfig
from repro.models import moe as MOE
from repro.models.common import init_params, tapping
from repro.obs import OBS

PCFG = ParallelConfig(attn_block_kv=32, xent_chunk=16, scan_chunk=16)


def _softmax2(a, b):
    return 1.0 / (1.0 + math.exp(b - a))


@pytest.mark.parametrize("logits,experts,weights", [
    # the runner-up within the mask threshold (0.005 < 0.02) shares the
    # first softmax; the second choice's only survivor takes weight 1
    ([2.0, 1.99, 1.0, -5.0], [0, 1], [_softmax2(2.0, 1.99), 1.0]),
    # just past the threshold: (1 - 0.979) / 1 = 0.021 > 0.02, masked
    ([1.0, 0.979, 0.5, 0.0], [0, 1], [1.0, 1.0]),
    # just inside it: 0.019 <= 0.02, kept
    ([1.0, 0.981, 0.5, 0.0], [0, 1], [_softmax2(1.0, 0.981), 1.0]),
    # a tie: the first index wins and the two share the softmax
    ([0.5, -1.0, 0.5, -2.0], [0, 2], [0.5, 1.0]),
    # a negative max: the scale is |s_e|, not m
    ([-1.0, -1.01, -3.0, -1.5], [0, 1], [_softmax2(-1.0, -1.01), 1.0]),
])
def test_sparsemixer_hand_worked(logits, experts, weights):
    w, e = MOE.sparsemixer(jnp.asarray([logits], jnp.float32), 2, 0.01)
    assert e.tolist() == [experts]
    np.testing.assert_allclose(np.asarray(w)[0], weights, rtol=1e-5)


def test_sparsemixer_does_not_renormalize():
    w, _ = MOE.sparsemixer(jnp.asarray([[1.0, 0.995, 0.99, -1.0]]), 2, 0.01)
    # 0.335 of a three-way softmax and 0.501 of a two-way one
    np.testing.assert_allclose(np.asarray(w)[0], [0.33500, 0.50125],
                               rtol=1e-4)


def test_registry_chooses_the_router():
    assert get_config("phi3.5-moe-42b-a6.6b").moe.router == "sparsemixer"
    assert get_config("phi3.5-moe-42b-a6.6b").moe.router_jitter == 0.01
    assert get_config("llama4-scout-17b-a16e").moe.router == "softmax"


def _one_expert_cfg():
    """16 experts, a zero router: every logit ties, so every token takes
    experts 0 and 1 (weights 1/16 and 1/15)."""
    cfg = reduced(get_config("phi3.5-moe-42b-a6.6b"))
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=16))


@pytest.fixture
def obs_enabled():
    OBS.reset()
    OBS.enable()
    yield OBS
    OBS.reset()
    OBS.disable()


def test_dropless_every_token_to_one_expert(obs_enabled):
    cfg = _one_expert_cfg()
    p = init_params(jax.random.PRNGKey(0), MOE.moe_schema(cfg))
    p["router"] = jnp.zeros_like(p["router"])
    B, S, D = 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)
    with tapping() as taps:
        y, _ = MOE.moe_mixer(p, x, cfg=cfg, pcfg=PCFG, train=False)
    assert np.asarray(taps["moe.experts"]).reshape(-1, 2).tolist() == \
        [[0, 1]] * (B * S)

    def expert(e, xt):
        h = jax.nn.silu(xt @ p["w_gate"][e]) * (xt @ p["w_up"][e])
        return h @ p["w_down"][e]
    xt = x.reshape(-1, D)
    want = expert(0, xt) / 16 + expert(1, xt) / 15
    np.testing.assert_allclose(np.asarray(y).reshape(-1, D),
                               np.asarray(want), rtol=2e-5, atol=2e-6)
    gauges = {name: m["series"][0]
              for name, m in OBS.snapshot()["metrics"].items()
              if name.startswith("moe_")}
    assert gauges["moe_assignments"]["value"] == B * S * 2
    assert gauges["moe_dispatch_rows"]["value"] == 16 * B * S
    assert gauges["moe_assignments"]["labels"]["mode"] == "serve"

    # training keeps its capacity: 1.25 * 32 * 2 / 16 slots, most dropped
    y_tr, _ = MOE.moe_mixer(p, x, cfg=cfg, pcfg=PCFG, train=True)
    kept = np.abs(np.asarray(y_tr).reshape(-1, D)).sum(-1) > 0
    assert 0 < kept.sum() < B * S


def test_session_with_params_draws_nothing(monkeypatch):
    import repro.models.common as common
    from repro.launch.serve import ServeSession
    from repro.models.model import model_schema
    cfg = reduced(get_config("phi3.5-moe-42b-a6.6b"))
    params = init_params(jax.random.PRNGKey(3), model_schema(cfg),
                         dtype=jnp.bfloat16)

    def refuse(*a, **k):
        raise AssertionError("the session drew weights of its own")
    monkeypatch.setattr(common, "init_params", refuse)
    sess = ServeSession("phi3.5-moe-42b-a6.6b", batch=2, prompt_len=8,
                        gen=2, params=params)
    assert sess.params is params
    out = sess.prefill(all_positions=True, taps=True)
    assert out["logits"].shape == (2, 8, cfg.padded_vocab)
    layers = out["taps"]["layers"]
    assert len(layers) == cfg.num_layers
    assert layers[0]["moe.experts"].shape == (2, 8, 2)
    assert layers[0]["x_in"].shape == (2, 8, cfg.d_model)
    assert sess.generate()["tokens"].shape == (2, 2)
    assert sess.prefill_traces == 2    # generate's prefill is the default
