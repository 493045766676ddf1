"""Each traffic driver at CPU-test widths: the same seed, here one past
32 bits, serves the same requests and tokens."""
import pytest

from bench import harness as H
from bench.tests import tiny

CELLS = sorted(w["name"] for w in H.benchmark()["workloads"])


def served(workload, seed, steps=2):
    ctx, drv = tiny.ctx_for(workload, seed)
    st = drv.setup(ctx)
    recs = [drv.step(ctx, st) for _ in range(steps)]
    out = [(r.prompt.tolist(), list(r.out))
           for _, r in sorted(st["eng"].requests.items())]
    ctx.free_program(st)
    return out, recs


@pytest.mark.parametrize("workload", CELLS)
def test_same_seed_same_service(workload):
    a, ra = served(workload, 2 ** 31 + 3)
    b, rb = served(workload, 2 ** 31 + 3)
    assert a == b
    assert all(r["rows"] > 0 and r["launches"] for r in ra)
    assert [r["rows"] for r in ra] == [r["rows"] for r in rb]
