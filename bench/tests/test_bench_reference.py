"""The plain reference's crossbar pieces: the wordline drive, continuous
at a zero activation once the overdrive is off, and the control
precisions of the net's contractions, each further from f32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import crossbar as xb


@pytest.mark.parametrize("overdrive,jump", [(True, xb.V_TH / xb.V_READ),
                                            (False, 0.0)])
def test_drive_at_a_zero_activation(overdrive, jump):
    x = jnp.asarray([[1.0, 1e-6, -1e-6, 0.0]])
    v, xs = xb._drive(x, 1, 1, 4, overdrive)
    v = np.asarray(v).reshape(2, 4)
    assert float(xs) == 1.0
    assert v[0, 0] == pytest.approx(1.0)
    assert v[0, 1] == pytest.approx(jump, abs=1e-5)      # positive rail
    assert v[1, 2] == pytest.approx(jump, abs=1e-5)      # negative rail
    assert v[0, 2] == v[1, 1] == v[0, 3] == v[1, 3] == 0.0


def test_control_precisions_are_each_further_from_f32():
    ka, kb = jax.random.split(jax.random.PRNGKey(3))
    a = jax.random.normal(ka, (8, 256))
    b = jax.random.normal(kb, (256, 16))
    ref = np.asarray(xb.einsum("ik,kn->in", a, b), np.float64)
    err = {p: np.abs(np.asarray(xb.einsum("ik,kn->in", a, b, p)) - ref).max()
           for p in ("high", "bf16")}
    assert 0 < err["high"] < err["bf16"] / 20
    with pytest.raises(ValueError):
        xb.einsum("ik,kn->in", a, b, "fp4")
