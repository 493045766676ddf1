"""Work of one call of a served model with sparse experts, counted as
routed: each token row computes its ``num_experts_per_tok`` experts of
``num_local_experts`` (a SiLU-gated expert is 3 d f multiply-adds), not
the padded rows a dropless dispatch may compute."""
from __future__ import annotations


def model_flops(ctx, call: dict) -> int:
    """Operations of one call: the kernel's count at every crossbar
    launch; twice the digital weights each row touches (the projections
    off the crossbar, the router, its routed experts, the LM head); and
    attention's scores and mixing over each row's context."""
    from bench.harness import load_module
    m, c = ctx.model, ctx.conf["config"]
    xc = ctx.conf.get("crossbar", {})
    layers = tuple(xc.get("layers", ()))
    analog = lambda tag: any(tag.startswith(l) for l in layers)
    d, f, hd = m["d_model"], m["d_ff"], m["head_dim"]
    qf, kvf = m["num_heads"] * hd, m["num_kv_heads"] * hd
    per_row = sum(0 if analog(tag) else n
                  for tag, n in (("attn.q", d * qf), ("attn.k", d * kvf),
                                 ("attn.v", d * kvf), ("attn.o", qf * d)))
    per_row += d * c["num_local_experts"]
    per_row += c["num_experts_per_tok"] * 3 * d * f
    per_row = per_row * m["num_layers"] + d * m["vocab_size"]
    total = 2 * per_row
    if layers:
        k = load_module("kernels", "emulator_block_unified")
        total += sum(k.flops(1, kk, nn, xc["geometry"])
                     for kk, nn in ctx.site_shapes)
    total *= call["rows"]
    return total + 4 * qf * call.get("ctx_sum", 0) * m["num_layers"]
