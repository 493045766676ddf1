"""The serving path's spans and named scopes (docs/observability.md):

  * the engine's and session's spans are recorded with telemetry on,
    one ``engine_tick`` per ``step()``, and nothing with it off;
  * the neutrality gate extended to ``ContinuousBatchEngine``: the same
    tokens and the same trace counts with telemetry on and off;
  * the compiled HLO of an executor forward carries ``analog:<site>``,
    ``emu_layout_g`` and ``emu_layout_io`` in its op_name metadata;
  * ``tools/profile_report.py`` on a CPU profile: spans recorded with
    ``profiler=True`` land on the host plane on the ops' clock, device
    ops resolve to their named scopes, and an idle gap is named by the
    program span over it.
"""
import glob
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import OBS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

ARCH = "gemma3-1b"
P, G = 8, 4

ENGINE_SPANS = ("engine_tick", "engine_admit", "engine_pack",
                "engine_dispatch", "engine_fetch", "engine_update")


@pytest.fixture
def obs_enabled():
    OBS.reset()
    OBS.enable()
    yield OBS
    OBS.reset()
    OBS.disable()
    OBS.profiler = False


def _prompts(n, vocab, seed=1):
    key = jax.random.PRNGKey(seed)
    return [np.asarray(jax.random.randint(jax.random.fold_in(key, i), (P,),
                                          0, vocab), np.int32)
            for i in range(n)]


def _analog_engine():
    from repro.configs.base import AnalogConfig
    from repro.configs.rram_ps32 import CASE_A
    from repro.core.analog import AnalogExecutor
    from repro.launch.batching import ContinuousBatchEngine
    from repro.launch.serve import ServeSession
    ex = AnalogExecutor(acfg=AnalogConfig(backend="analytic",
                                          layers=("mlp",)), geom=CASE_A)
    sess = ServeSession(ARCH, reduced=True, batch=1, prompt_len=P, gen=G,
                        seed=0, executor=ex)
    return ContinuousBatchEngine(sess, max_slots=2, max_len=P + G)


def _series(met, name):
    return met[name]["series"] if name in met else []


def _count(met, name):
    return sum(s["count"] for s in _series(met, name + "_seconds"))


def test_engine_spans_one_tick_per_step(obs_enabled):
    eng = _analog_engine()
    prompts = _prompts(2, eng.cfg.vocab_size)
    for p in prompts:
        eng.submit(p, max_new=G)
    steps = 0
    while eng.busy:
        eng.step()
        steps += 1
    met = OBS.snapshot()["metrics"]
    assert _count(met, "engine_tick") == steps == G - 1
    for name in ENGINE_SPANS[1:]:
        assert _count(met, name) == steps, name
    assert _count(met, "engine_prefill") == len(prompts)
    for name in ("session_init", "session_sites", "session_states",
                 "engine_build", "engine_refresh_states"):
        assert _count(met, name) == 1, name
    # one plan and one state built per analog call site
    sites = len(eng.session.sites())
    assert sites > 0
    for name in ("analog_plan_build", "analog_state_build"):
        assert _count(met, name) == sites, name
    # the phases lie inside their tick
    tick = sum(s["sum"] for s in _series(met, "engine_tick_seconds"))
    parts = sum(s["sum"] for n in ENGINE_SPANS[1:]
                for s in _series(met, n + "_seconds"))
    assert 0 < parts <= tick


def test_engine_telemetry_is_trace_and_bit_neutral(obs_enabled):
    """Same tokens and same trace counts with telemetry on and off, and
    nothing recorded while it is off."""
    runs = {}
    for on in (False, True):
        OBS.enabled = on
        eng = _analog_engine()
        toks = eng.run(_prompts(2, eng.cfg.vocab_size), max_new=G)
        runs[on] = (toks, eng.decode_traces, eng.prefill_traces,
                    eng.session.ex._fns.keys())
        if not on:
            assert OBS.snapshot()["metrics"] == {}
    (t_off, d_off, p_off, f_off), (t_on, d_on, p_on, f_on) = \
        runs[False], runs[True]
    assert (d_off, p_off) == (d_on, p_on) == (1, 1)
    assert list(f_off) == list(f_on)
    for a, b in zip(t_off, t_on):
        np.testing.assert_array_equal(a, b)


def test_executor_forward_hlo_carries_site_and_relayout_scopes():
    """The unified kernel (interpret mode on the CPU) and the wrapper's
    relayouts carry their site and part in the op_name metadata, in
    both dispatch modes; no relayout op's name holds the kernel's."""
    from repro.configs.base import AnalogConfig
    from repro.configs.rram_ps32 import CASE_A
    from repro.core import conv4xbar
    from repro.core.analog import AnalogExecutor
    from repro.models.common import init_params
    params = init_params(jax.random.PRNGKey(7),
                         conv4xbar.conv4xbar_schema(CASE_A, n_periph=2))
    ex = AnalogExecutor(acfg=AnalogConfig(backend="emulator"), geom=CASE_A,
                        emulator_params=params, use_pallas=True)
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (70, 3)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 70)) * 0.5
    tag = "mlp.up#0"
    st = ex.state_for(tag, w)
    texts = {
        "jit": ex._unified_for(tag, w).lower(x, st).compile().as_text(),
        "eager": jax.jit(lambda a: ex.matmul(a, w, tag, state=st))
        .lower(x).compile().as_text(),
    }
    for mode, text in texts.items():
        names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
                 for ln in text.splitlines() if 'op_name="' in ln]
        scoped = [n for n in names if f"analog:{tag}/" in n]
        assert scoped, mode
        for part in ("emu_layout_g", "emu_layout_io"):
            assert any(f"/{part}/" in n for n in scoped), (mode, part)
        for n in names:
            if "emu_layout" in n:
                assert "emulator_block_unified" not in n
                assert "tpu_custom_call" not in n


def test_profile_report_reads_scopes_spans_and_gaps(obs_enabled, tmp_path):
    """Spans recorded with ``profiler=True`` land on the host plane of a
    CPU profile; device ops resolve to their named scopes through the
    trace's HLO protos; ``host_spans`` clips to the window; an idle gap
    is named by the program span over it."""
    import profile_report as pr
    OBS.enable(profiler=True)

    def f(x):
        with jax.named_scope("analog:mlp.up#0"):
            with jax.named_scope("emu_layout_g"):
                y = jnp.tanh(x.T @ x)
            return y @ x.T

    fn = jax.jit(f)
    x = jnp.ones((128, 96), jnp.float32)
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with OBS.span("engine_tick"):
        time.sleep(0.002)
        with jax.profiler.TraceAnnotation("bench.call"):
            fn(x).block_until_ready()
            with OBS.span("engine_fetch"):
                time.sleep(0.02)                    # the device idles
            fn(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    p = pr.Profile(path)
    win = p.window("bench.call")
    assert {"engine_tick", "engine_fetch"} <= set(p.spans())
    (tick,) = p.host_spans("engine_tick", win)
    assert tick == win                              # clipped at both ends
    (fetch,) = p.host_spans("engine_fetch", win)
    assert win[0] < fetch[0] < fetch[1] < win[1]
    g_s, g_n = p.scope_time("emu_layout_g", win)
    site_s, site_n = p.scope_time("analog:mlp.up#0", win)
    assert g_n >= 2 and 0 < g_s <= site_s and site_n > g_n
    assert p.scope_time("emu_layout_io", win) == (0.0, 0)
    gaps = p.idle_gaps(win, 0.01)
    assert [g[2] for g in gaps] == ["engine_fetch"]
    rep = pr.report(p, win, 0.01)
    assert rep["sites"]["mlp.up#0"]["emu_layout_g"] == pytest.approx(g_s)
    assert rep["spans"]["engine_fetch"]["count"] == 1


def test_moe_scopes_reach_the_hlo_and_the_report(obs_enabled, tmp_path):
    """The MoE layer's router and expert ops carry ``moe_route`` and
    ``moe_experts`` in their ``op_name``; the report sums a scope's
    device time when asked (``--scope``)."""
    import dataclasses

    import profile_report as pr
    from repro.configs import get_config, reduced
    from repro.configs.base import ParallelConfig
    from repro.models import moe
    from repro.models.common import init_params
    cfg = reduced(get_config("phi3.5-moe-42b-a6.6b"))
    cfg = dataclasses.replace(cfg, d_ff=256)
    p = init_params(jax.random.PRNGKey(0), moe.moe_schema(cfg))
    pc = ParallelConfig()
    fn = jax.jit(lambda x: moe.moe_mixer(p, x, cfg=cfg, pcfg=pc,
                                         train=False)[0])
    x = jnp.ones((2, 64, cfg.d_model), jnp.float32)
    text = fn.lower(x).compile().as_text()
    names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in text.splitlines() if 'op_name="' in ln]
    for scope in ("moe_route", "moe_experts"):
        assert any(scope in n.split("/") for n in names), scope
    fn(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.call"):
        fn(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    prof = pr.Profile(path)
    win = prof.window("bench.call")
    rep = pr.report(prof, win, scopes=("moe_experts", "moe_route"))
    assert rep["scopes"]["moe_experts"]["ops"] > 0
    assert 0 < rep["scopes"]["moe_experts"]["s"] <= rep["device_ops_s"]
