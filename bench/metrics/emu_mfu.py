"""The whole step's share of the chip's bf16 peak in the traced window:
the model's work per call (the crossbar net's drive-dependent operations
at every analog site, twice the digital weights each row touches, and
attention's scores and mixing) times the calls made, over the window.
The kernel runs in f32; the bf16 peak is the yardstick all the same."""
from bench import sites as S


def read(r):
    if not r.launches():
        return None
    work = sum(S.model_flops(r.ctx, c) for c in r.calls)
    return 100.0 * work / (r.window_s * r.peaks()["bf16_flops_s"])
