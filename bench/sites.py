"""Shared pieces of the drivers: the crossbar launches of a call, the
reference in its precision, and the numbers that decide ``correct``."""
from __future__ import annotations

import numpy as np


def site_shapes(sess):
    """(K, N) of every crossbar site of the session's model."""
    return [tuple(int(d) for d in w.shape) for w in sess.sites().values()]


def launches(shapes, rows_per_call):
    """The crossbar kernel launches of one call: one per analog site and
    per program call in it, as (rows M, K, N)."""
    return [(m, k, n) for m in rows_per_call for k, n in shapes]


# each control: the reference one step below what the configuration
# states -- the crossbar net's f32 contractions at three bf16 passes or
# one, or the bf16 activations held in fp8
CONTROLS = {"high": {"precision": "high"}, "bf16": {"precision": "bf16"},
            "fp8": {"held": "fp8"}}


def reference(ctx, control: str = None):
    from bench.reference.decoder import Decoder
    return Decoder(ctx.model, ctx.conf.get("crossbar", {}), ctx.weights,
                   ctx.eparams, **CONTROLS.get(control, {}))


def row_errs(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: the widest gap between served and reference logits over
    the vocabulary, in units of that row's reference logit spread (NaN
    where the served row is not finite)."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.abs(served - ref).max(axis=-1) / ref.std(axis=-1)


def token_gaps(tokens, ref: np.ndarray) -> np.ndarray:
    """Per row: the gap by which the served token's reference logit lies
    below the reference's best, in units of the row's logit spread."""
    ref = np.asarray(ref, np.float64)
    tokens = np.asarray(tokens).reshape(-1)
    if tokens.min() < 0:
        raise ValueError(f"a checked row has no served token: {tokens}")
    best = ref.max(axis=-1)
    got = ref[np.arange(len(tokens)), tokens]
    return (best - got) / ref.std(axis=-1)


def summary(errs, gaps) -> dict:
    """The numbers a driver's ``compare`` reports over every checked row:
    the worst row of each (NaN if any row is not finite), the median
    row's widest gap, and each row's readings beside them."""
    worst = lambda a: float(np.max(a)) if np.all(np.isfinite(a)) \
        else float("nan")
    rows = lambda a: [round(float(x), 6) for x in a]
    return {"logit_err_max": worst(errs),
            "logit_err_median": float(np.median(errs)),
            "token_gap": worst(gaps), "logit_err_rows": rows(errs),
            "token_gap_rows": rows(gaps)}


def model_flops(ctx, call: dict) -> int:
    """Operations of one call of the served model: the kernel's count at
    every crossbar launch, twice the digital weights each row touches,
    and attention (scores and mixing) over each row's context."""
    from bench.harness import load_module
    m, xc = ctx.model, ctx.conf.get("crossbar", {})
    layers = tuple(xc.get("layers", ()))
    analog = lambda tag: any(tag.startswith(l) for l in layers)
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    qf, kvf = m["num_heads"] * hd, m["num_kv_heads"] * hd
    per_row = 0
    for tag, n in (("attn.q", d * qf), ("attn.k", d * kvf),
                   ("attn.v", d * kvf), ("attn.o", qf * d),
                   ("mlp.up", 3 * d * f)):
        per_row += 0 if analog(tag) else n
    per_row *= m["num_layers"]
    per_row += d * m["vocab_size"]
    total = 2 * per_row
    if layers:
        k = load_module("kernels", "emulator_block_unified")
        total += sum(k.flops(1, kk, nn, xc["geometry"])
                     for kk, nn in ctx.site_shapes)
    total *= call["rows"]
    return total + 4 * qf * call.get("ctx_sum", 0) * m["num_layers"]
