"""Batched prefill through ``ServeSession``, as an evaluation harness
scores prompts: each timed call is one teacher-forced prefill of
``batch`` prompts of ``prompt_len`` tokens, drawn uniformly from the
vocabulary with the seed, that returns the logits at every position, the
KV cache and each MoE layer's routes, and hands each prompt's one
generated token (``gen_len`` 1) back to the host.  Calls run back to
back (a closed loop).  The session serves the benchmark's weights
(``ServeSession(params=...)``), so no second copy is drawn.

Traffic keys: ``batch``, ``prompt_len``, ``gen_len`` (1: the call's
answer is each prompt's next token).

The call is the session's one prefill step with every position's logits
and its taps (``ServeSession.prefill(all_positions=True, taps=True)``):
each crossbar site's drive and output, the rotated q, each layer's input
and its expert block's input, routes and output, and the head's input.
``correct`` compares the last call with the plain reference
(``bench/reference/phimoe.py``) step by step, each step from the served
values before it (``PhiMoE.check``), every row judged by its worst
element (in units of the reference row's spread, and for the crossbar
sites and the attention's output of its largest value, ``_peak_errs``):
every row's logits at every position from the served head
input; every crossbar site of every layer on its served drive; the
attention of the served q, keys and values; each expert block with the
routes followed and checked (``route_flips``); the norms, biases, RoPE
and residual adds; and the KV cache the prefill leaves for the decode
that would follow.
"""
from __future__ import annotations

import inspect

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness as H
from bench import sites as S


class Served:
    """The requests served so far, ``rid -> Request``: the record every
    driver keeps as ``state["eng"].requests``."""

    def __init__(self):
        self.requests = {}

    def add(self, prompts, answers):
        from repro.launch.batching import DONE, Request
        for p, a in zip(prompts, answers):
            rid = len(self.requests)
            self.requests[rid] = Request(rid=rid, prompt=p, max_new=1,
                                         status=DONE, out=[int(a)])


def setup(ctx):
    from repro.launch.serve import ServeSession
    if "params" not in inspect.signature(ServeSession).parameters:
        raise SystemExit("session_eval: this ServeSession takes no params=, "
                         "so it would hold a second copy of the weights")
    t = ctx.traffic
    assert t["gen_len"] == 1, "a call answers with one token per prompt"
    with ctx.phase("init"):
        ctx.make_executor()
        with H.registered(ctx.arch):
            sess = ServeSession(ctx.arch.name, reduced=False,
                                batch=t["batch"], prompt_len=t["prompt_len"],
                                gen=t["gen_len"], seed=ctx.seed % (2 ** 31),
                                executor=ctx.executor, params=ctx.weights)
    ctx.site_shapes = S.site_shapes(sess)
    st = {"sess": sess, "eng": Served(), "cache": None}
    with ctx.phase("plans_states"):
        st["states"] = sess.states()
        jax.block_until_ready(st["states"])

    def served(params, tokens, cache, pos, states):
        """The session's prefill (of the session's own ``params``) in the
        form the planted faults wrap: the cache handed in is the last
        call's, which the prefill replaces."""
        out = sess.prefill(tokens, states, all_positions=True, taps=True)
        st["taps"] = out["taps"]
        return out["logits"], out["cache"]

    st["decode"] = served
    with ctx.phase("compile_warmup"):
        _call(ctx, st)
    return st


def _call(ctx, st):
    t = ctx.traffic
    B, P = t["batch"], t["prompt_len"]
    V = ctx.model["vocab_size"]
    tokens = ctx.rng.integers(0, V, (B, P), dtype=np.int32)
    logits, st["cache"] = st["decode"](st["sess"].params, jnp.asarray(tokens),
                                       st["cache"], 0, st["states"])
    answers = np.asarray(jnp.argmax(logits[:, -1, :V], axis=-1))
    st["eng"].add(tokens, answers)
    st["last"] = (tokens, logits, st["cache"], st["taps"])
    return {"rows": B * P, "launches": S.launches(ctx.site_shapes, [B * P]),
            "ctx_sum": B * P * (P + 1) // 2}


def step(ctx, st):
    return _call(ctx, st)


def _layers_kv(cache):
    """A served cache as one array (L, 2, B, S, Hkv, Dh)."""
    kv = cache["scan"]["p0"]["attn"]
    return np.stack([np.asarray(kv["k"], np.float32),
                     np.asarray(kv["v"], np.float32)], axis=1)


def check(ctx, st):
    """The last call's prompts and what it served, on the host."""
    tokens, logits, cache, taps = st["last"]
    V = ctx.model["vocab_size"]
    return {"tokens": tokens,
            "logits": np.asarray(logits[..., :V], np.float32),
            "kv": _layers_kv(cache),
            "taps": jax.tree.map(np.asarray, taps)}


def reference(ctx, control: str = None):
    from bench.reference.phimoe import TAU, PhiMoE
    return PhiMoE(ctx.model, ctx.conf.get("crossbar", {}), ctx.weights,
                  ctx.eparams, routing=ctx.conf["config"], tau=TAU,
                  **S.CONTROLS.get(control, {}))


def _ref_kv(cache):
    return np.stack([np.stack([np.asarray(k), np.asarray(v)])
                     for k, v in cache])


def kv_err(served: np.ndarray, ref: np.ndarray) -> float:
    """The widest gap between the served and reference keys or values of
    any layer, in units of that layer's and tensor's reference spread
    (NaN if any served entry is not finite)."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(served)):
        return float("nan")
    axes = tuple(range(2, ref.ndim))
    return float((np.abs(served - ref).max(axis=axes)
                  / ref.std(axis=axes)).max())


def _peak_errs(served, ref) -> np.ndarray:
    """Per row: the widest gap between served and reference values, in
    units of the reference row's largest magnitude.  A bf16 rounding of
    the row's output moves it by at most one ulp, 2^-8 to 2^-7 of the
    value it rounds; a row's spread can lie 50 times below its largest
    value, so there a single rounding reads 0.2 spreads."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.abs(served - ref).max(axis=-1) / np.abs(ref).max(axis=-1)


def _worst(pairs, median: bool = False, errs=S.row_errs) -> float:
    """Over pairs (served, reference) of (rows, width): the widest gap of
    a row, in units of the reference row's spread (``sites.row_errs``) or
    as ``errs`` gives it, the worst row of all, or with ``median`` the
    median row of the worst pair; NaN if any served entry is not
    finite."""
    rows = [errs(np.asarray(a), np.asarray(b)) for a, b in pairs]
    if not all(np.all(np.isfinite(e)) for e in rows):
        return float("nan")
    return float(max(np.median(e) if median else e.max() for e in rows))


def compare(ctx, data, controls=()):
    """The last call against the reference, step by step on what was
    served; for each control (``sites.CONTROLS``), the reference at that
    lower precision put in the program's place and judged the same way."""
    got = {"program": (data["logits"], data["kv"], data["taps"])}
    for c in controls:
        logits, cache, taps = reference(ctx, c).prefill(data["tokens"])
        got[c] = (logits, _ref_kv(cache), jax.tree.map(np.asarray, taps))
    out = {}
    for k, (logits, kv, taps) in got.items():
        ref = reference(ctx)
        r = ref.check(data["tokens"], [(a[0], a[1]) for a in kv], taps)
        V = r["logits"].shape[-1]
        served = logits.reshape(-1, V)
        nums = S.summary(S.row_errs(served, r["logits"]),
                         S.token_gaps(served.argmax(-1), r["logits"]))
        nums["kv_err"] = kv_err(kv, _ref_kv(r["kv"]))
        nums["site_err_max"] = _worst(r["site"], errs=_peak_errs)
        nums["mix_err_max"] = _worst(r["mix"], errs=_peak_errs)
        nums["mix_err_median"] = _worst(r["mix"], median=True)
        nums["site_err_spread"] = _worst(r["site"])
        nums["mix_err_spread"] = _worst(r["mix"])
        nums["ffn_err_max"] = _worst(r["ffn"])
        nums["glue_err_max"] = _worst(r["glue"])
        nums.update(ref.stats)
        out[k] = nums
    return out
