"""The trace reduction on a small profile recorded on the CPU."""
import glob
import os
import types

import pytest


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from bench.harness import Spans
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((192, 192), jnp.float32)
    f(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("prof"))
    spans = Spans(profile=True)
    calls = []
    jax.profiler.start_trace(d)
    for _ in range(3):
        with spans.span("bench.call"):
            f(x).block_until_ready()
        calls.append({"rows": 2, "launches": []})
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    return path, calls


def _ctx():
    return types.SimpleNamespace(cell={"chips": 1}, device_kind="cpu",
                                 conf={}, model={})


def test_window_busy_and_breakdown(profile):
    from bench import trace as tr
    path, calls = profile
    dev, host = tr.load_events(path)
    r = tr.Reading(dev, host, calls, _ctx())
    assert r.window_s > 0
    assert 0 < r.busy_s <= r.window_s
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"])
    assert len(b["idle_gaps"]) <= 10
    t, n = r.kernel_time(("no-such-kernel",))
    assert (t, n) == (0.0, 0)


def test_unknown_device_has_no_peaks(profile):
    from bench import trace as tr
    path, calls = profile
    r = tr.Reading(*tr.load_events(path), calls, _ctx())
    with pytest.raises(KeyError):
        r.peaks()


def test_merge_and_idle_reader():
    from bench import harness as H
    from bench import trace as tr
    assert tr.merge([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    idle = H.load_module("metrics", "idle_share.emu")
    r = types.SimpleNamespace(window_s=2.0, busy_s=1.5)
    assert idle.read(r) == pytest.approx(25.0)


def test_roofline_reader_is_silent_without_the_kernel(profile):
    from bench import harness as H
    from bench import trace as tr
    path, calls = profile
    r = tr.Reading(*tr.load_events(path), calls, _ctx())
    assert H.load_module("metrics", "emu_kernel_roofline").read(r) is None


def test_roofline_reader_is_silent_when_other_ops_match():
    from bench import harness as H
    k = H.load_module("kernels", "emulator_block_unified")
    read = H.load_module("metrics", "emu_kernel_roofline").read
    launch = (4, 7168, 19200)

    def reading(n_ops):
        return types.SimpleNamespace(
            kernel=lambda name: k,
            kernel_time=lambda names: (0.9 * n_ops, n_ops),
            launches=lambda: [launch],
            peaks=lambda: {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9},
            ctx=types.SimpleNamespace(
                conf={"crossbar": {"geometry": "rram_ps32_a"}}))
    assert 0 < read(reading(1)) < 100
    assert read(reading(2)) is None      # a second Pallas kernel matched
