"""A run with the timed path broken underneath comes out not correct,
once for each fault a serving cell can have (a decode step that keeps
its cache; answers altered where they are produced, in every slot or in
one; no serving call takes a mean over its batch); the control, put in
the program's place and judged by the same comparison, does too."""
import pytest

from bench import harness as H
from bench.tests import faults, tiny

CELLS = sorted(w["name"] for w in H.benchmark()["workloads"])
FAULTS = ("token_altered", "slot_altered", "state_unchanged")


@pytest.mark.parametrize("workload,fault",
                         [(c, f) for c in CELLS for f in FAULTS])
def test_fault_is_not_correct(workload, fault):
    # long enough that the last tick's rows hold many decoded positions
    res = tiny.run(workload, 7, seconds=3.0, fault=getattr(faults, fault))
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    res = tiny.run(workload, 7, controls=("fp8",))
    assert res["correct"] is True, res["checks"]
    assert res["controls"]["fp8"]["correct"] is False, res["controls"]
