"""The whole step's share of the chip's bf16 peak in the traced window,
for a model with sparse experts: the model's work per call with each
token's experts counted as routed (``bench/moe.py``) times the calls
made, over the window."""
from bench import moe


def read(r):
    if not r.launches():
        return None
    work = sum(moe.model_flops(r.ctx, c) for c in r.calls)
    return 100.0 * work / (r.window_s * r.peaks()["bf16_flops_s"])
