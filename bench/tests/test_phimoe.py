"""Phi-3.5-MoE against its plain reference (``bench/reference/phimoe.py``)
on the CPU at small width, with nonzero biases; the route check; the
cell's planted MoE and crossbar-site faults; and `session_eval`'s refusal
of a session that takes no weights."""
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H
from bench import moe as bench_moe
from bench import sites as S
from bench.reference.phimoe import TAU, PhiMoE, sparsemixer
from bench.tests import tiny

CELL = "phi35moe.emu-prefill"
LIMITS = H.load_json("limits", CELL + ".json")
# a whole forward from the tokens, two layers at width 64, in units of a
# row's logit spread (the cell's step-by-step check reads 0 at the
# head): digital rows read up to 0.034, emulated ones up to 0.87 at a
# decode step (emulated attention amplifies a rounding, as PERF.md
# section 2 says), medians 0.022 and 0.060 (my CPU runs); a wrong bias,
# route or cache reads several spreads
FORWARD = {"digital": {"max": 0.1, "median": 0.05},
           "emulator": {"max": 1.5, "median": 0.15}}
ARCH = "phi3.5-moe-42b-a6.6b"
B, P, G = 2, 8, 3


def _conf():
    return H.load_json("configs", "phi3.5-moe.x1.json")


def test_registry_agrees_with_the_file():
    from repro.configs import get_config
    c, reg = _conf()["config"], get_config(ARCH)
    assert reg.qkv_bias and reg.o_bias and c["attention_bias"]
    assert reg.head_bias and c["lm_head_bias"]
    assert reg.moe.router == "sparsemixer"
    assert reg.moe.router_jitter == c["router_jitter_noise"]
    assert reg.norm == "layernorm" and c["rms_norm_eps"] == 1e-5
    assert _conf()["crossbar"]["layers"] == ["attn"]


# --------------------------------------------------------------------------- #
# the route check
# --------------------------------------------------------------------------- #
def _router(tau=TAU):
    return PhiMoE({"norm_eps": 1e-5}, {}, None, None,
                  routing={"num_experts_per_tok": 2,
                           "router_jitter_noise": 0.01}, tau=tau)


def test_admissibility_rejects_a_planted_non_tie_flip():
    # row 0: no tie anywhere; row 1: experts 0 and 1 tied within tau
    s = np.asarray([[3.0, 1.0, 0.0, -1.0], [2.0, 1.99, 0.0, -2.0]],
                   np.float32)
    e, w = sparsemixer(s, 2, 0.01)
    assert e.tolist() == [[0, 1], [0, 1]]
    r = _router()
    r._route(s, (e, w))
    assert r.stats == {"route_flips": 0, "routes_replayed": 0}
    flipped = e.copy()
    flipped[0, 1] = 3                      # 2 logits below the runner-up
    r._route(s, (flipped, w))
    assert r.stats["route_flips"] == 1
    tied = np.asarray([[0, 1], [1, 0]])    # the tie taken the other way
    r = _router()
    r._route(s, (tied, np.asarray([[1.0, 1.0], [0.5, 1.0]], np.float32)))
    assert r.stats == {"route_flips": 0, "routes_replayed": 1}
    dropped = w.copy()
    dropped[:, 1] = 0.0                    # a second expert dropped
    r = _router()
    r._route(s, (e, dropped))
    assert r.stats["route_flips"] == 2


# --------------------------------------------------------------------------- #
# program against reference, prefill and prefill-then-decode
# --------------------------------------------------------------------------- #
def _model(cfg):
    return {"num_layers": cfg.num_layers, "d_model": cfg.d_model,
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_base,
            "norm_eps": 1e-5}


def _with_biases(params, key):
    """Every bias leaf drawn nonzero (the schema makes them zero)."""
    names = ("bq", "bk", "bv", "bo", "b", "head_bias")
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, v) in enumerate(flat):
        if jax.tree_util.keystr(path[-1:]).strip("[]'") in names:
            v = (0.5 * jax.random.normal(jax.random.fold_in(key, i), v.shape)
                 ).astype(v.dtype)
        out.append(v)
    return jax.tree_util.tree_unflatten(tree, out)


def _recorded_routes(monkeypatch):
    """Every routing decision the program makes, in call order."""
    from repro.models import moe
    got, orig = [], moe._route

    def route(logits, mcfg):
        w, e = orig(logits, mcfg)
        jax.debug.callback(lambda e, w: got.append((np.asarray(e),
                                                    np.asarray(w))), e, w)
        return w, e
    monkeypatch.setattr(moe, "_route", route)
    return got


@pytest.mark.parametrize("backend", ["digital", "emulator"])
def test_prefill_and_decode_against_reference(backend, monkeypatch):
    from repro.configs import get_config, reduced
    from repro.launch.serve import ServeSession
    from repro.models import model as M
    cfg = reduced(get_config(ARCH))
    crossbar = ({"backend": "emulator", "layers": ["attn"],
                 "geometry": "rram_ps32_a", "wl_overdrive": False}
                if backend == "emulator" else {})
    params, eparams = H.make_weights(cfg, crossbar.get("geometry"), 11)
    params = _with_biases(params, jax.random.PRNGKey(12))
    ex = None
    if crossbar:
        from repro.configs.base import AnalogConfig
        from repro.configs.rram_ps32 import BLOCKS
        from repro.core.analog import AnalogExecutor
        ex = AnalogExecutor(
            acfg=AnalogConfig(enabled=True, backend="emulator",
                              layers=("attn",), wl_overdrive=False),
            geom=BLOCKS["rram_ps32_a"], emulator_params=eparams)
    routes = _recorded_routes(monkeypatch)
    sess = ServeSession(ARCH, batch=B, prompt_len=P, gen=G, executor=ex,
                        params=params)
    toks = np.random.default_rng(13).integers(0, cfg.vocab_size, (B, P + G))
    states = sess.states() if ex is not None else {}
    out = sess.prefill(jnp.asarray(toks[:, :P], jnp.int32), states,
                       all_positions=True, taps=True)
    L = cfg.num_layers

    def served(n):                       # the last n decisions, per layer
        e = np.stack([e for e, _ in routes[-n:]])
        w = np.stack([w for _, w in routes[-n:]])
        return e, w

    ref = PhiMoE(_model(cfg), crossbar, params, eparams,
                 routing={"num_experts_per_tok": 2,
                          "router_jitter_noise": 0.01}, tau=TAU)
    taps = jax.tree.map(np.asarray, out["taps"])
    taken = tuple(np.stack([t["moe." + k] for t in taps["layers"]])
                  for k in ("experts", "weights"))
    np.testing.assert_array_equal(taken[0].reshape(L, -1, 2), served(L)[0])
    r_logits, r_cache, _ = ref.prefill(toks[:, :P], served=taken)
    V = cfg.vocab_size
    got = np.asarray(out["logits"])[..., :V].reshape(-1, V)
    errs = S.row_errs(got, r_logits.reshape(-1, V))
    assert errs.max() < FORWARD[backend]["max"], errs
    assert np.median(errs) < FORWARD[backend]["median"], errs
    # and step by step on the served values, as the cell checks it
    kv = out["cache"]["scan"]["p0"]["attn"]
    r = ref.check(toks[:, :P], list(zip(np.asarray(kv["k"], np.float32),
                                        np.asarray(kv["v"], np.float32))),
                  taps)
    assert S.row_errs(got, r["logits"]).max() < LIMITS["logit_err_max"]
    drv = H.load_module("drivers", "session_eval")
    nums = {"site_err_max": drv._worst(r["site"], errs=drv._peak_errs),
            "mix_err_max": drv._worst(r["mix"], errs=drv._peak_errs),
            "mix_err_median": drv._worst(r["mix"], median=True),
            "ffn_err_max": drv._worst(r["ffn"]),
            "glue_err_max": drv._worst(r["glue"])}
    for name, val in nums.items():
        assert val < LIMITS[name], (name, val)

    cache = M.zeros_cache(M.model_cache_schema(cfg, B, P + G))
    cache = jax.tree.map(
        lambda z, c: jax.lax.dynamic_update_slice(z, c.astype(z.dtype),
                                                  (0,) * z.ndim),
        cache, out["cache"])
    for i in range(G):
        tok = jnp.asarray(toks[:, P + i:P + i + 1], jnp.int32)
        logits, cache = sess._decode(sess.params, tok, cache,
                                     jnp.asarray(P + i, jnp.int32), states)
        r_logits, r_cache = ref.decode(toks[:, P + i:P + i + 1], r_cache,
                                       P + i, served=served(L))
        errs = S.row_errs(np.asarray(logits)[:, :V], r_logits)
        assert errs.max() < FORWARD[backend]["max"], (i, errs)
    assert ref.stats["route_flips"] == 0, ref.stats


# --------------------------------------------------------------------------- #
# the cell's MoE faults, planted in the program's router
# --------------------------------------------------------------------------- #
def _plant(monkeypatch, change):
    """A fault in the served model's routing of its first layer: the
    router is wrapped so that, where the layer's router weights are the
    first layer's, ``change(multipliers, experts, logits)`` replaces its
    choices; the session's steps are rebuilt so the next call traces the
    fault."""
    from repro.models import blocks, moe

    def fault(ctx, st):
        first = ctx.weights["decoder"]["scan"]["p0"]["ff"]["router"][0]
        here = {}
        inner_mixer, inner_route = blocks.moe_mixer, moe._route

        def mixer(params, x, **kw):
            here["first"] = jnp.all(params["router"] == first)
            return inner_mixer(params, x, **kw)

        def route(logits, mcfg):
            w, e = inner_route(logits, mcfg)
            w2, e2 = change(w, e, logits)
            return (jnp.where(here["first"], w2, w),
                    jnp.where(here["first"], e2, e))
        monkeypatch.setattr(blocks, "moe_mixer", mixer)
        monkeypatch.setattr(moe, "_route", route)
        st["sess"]._steps_built = False
    return fault


def _second_expert_dropped(w, e, logits):
    return w.at[:, 1].set(0.0), e


def _route_flipped(w, e, logits):
    """The first token's second expert replaced by its lowest-rated one."""
    return w, e.at[0, 1].set(jnp.argmin(logits[0]).astype(e.dtype))


@pytest.mark.parametrize("change", [_second_expert_dropped, _route_flipped],
                         ids=["second_expert_dropped", "route_flipped"])
def test_moe_fault_is_not_correct(change, monkeypatch):
    res = tiny.run(CELL, 7, fault=_plant(monkeypatch, change))
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["route_flips"]["value"] > 0, res["checks"]


# --------------------------------------------------------------------------- #
# the cell's crossbar-site faults, planted in the executor's output
# --------------------------------------------------------------------------- #
def _site_row_off(tag, gain=1.1, row=3):
    """A fault in one crossbar site of every layer: one row of its output
    (the call's fourth) off by ``gain``, as a gain error on one input
    row's column reads would put it; the rest served as they are."""
    def fault(ctx, st):
        ex = st["sess"].ex
        inner = ex.hook

        def hook(x, w, t):
            out = inner(x, w, t)
            if t != tag or out is None:
                return out
            o2 = out.reshape(-1, out.shape[-1])
            return o2.at[row].multiply(gain).reshape(out.shape)
        ex.hook = hook
        st["sess"]._steps_built = False
    return fault


@pytest.mark.parametrize("tag", ["attn.q", "attn.o"])
def test_site_fault_is_not_correct(tag):
    res = tiny.run(CELL, 7, fault=_site_row_off(tag))
    assert res["correct"] is False, res["checks"]
    site = res["checks"]["site_err_max"]
    assert site["value"] > site["limit"], res["checks"]


# --------------------------------------------------------------------------- #
# the driver and the work count
# --------------------------------------------------------------------------- #
def test_driver_stops_before_weights_without_session_params(monkeypatch):
    import repro.launch.serve as serve

    class OldSession:                    # a ServeSession that takes no params
        def __init__(self, arch, *, reduced=True, batch=4, prompt_len=32,
                     gen=16, seed=0, executor=None):
            raise AssertionError("not reached")
    assert "params" in inspect.signature(serve.ServeSession).parameters
    monkeypatch.setattr(serve, "ServeSession", OldSession)
    ctx, drv = tiny.ctx_for(CELL, 7)
    with pytest.raises(SystemExit):
        drv.setup(ctx)
    assert ctx.weights is None and ctx.executor is None


def test_moe_work_counts_routed_experts():
    ctx, _ = tiny.ctx_for(CELL, 7)
    m = ctx.model
    d, f, V, L = m["d_model"], m["d_ff"], m["vocab_size"], m["num_layers"]
    ctx.site_shapes = []                 # no crossbar launch counted
    ctx.conf = dict(ctx.conf, crossbar={})
    qf, kvf = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    per_row = L * (2 * d * qf + 2 * d * kvf + d * 16 + 2 * 3 * d * f) + d * V
    call = {"rows": 32, "ctx_sum": 272}
    assert bench_moe.model_flops(ctx, call) == \
        2 * per_row * 32 + 4 * qf * 272 * L
