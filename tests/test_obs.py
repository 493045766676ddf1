"""Telemetry subsystem (repro.obs, docs/observability.md):

  * registry semantics: label series, gauge set/add, histogram bucket
    edges, kind-conflict rejection, thread-safety under a
    ``ThreadPoolExecutor``;
  * disabled mode really is a no-op: ``NULL_SPAN``, nothing recorded,
    instrumented hot paths leave the registry empty;
  * the neutrality contract: with telemetry ON the executor's jit trace
    counts AND the f32 outputs are bit-identical to telemetry OFF;
  * exporters round-trip: JSON snapshot -> Prometheus text -> parsed
    values; ``diff_snapshots`` zeroes counters against themselves;
  * ``RecompileSentinel`` passes a compile-once block and raises
    ``RecompileError`` (strict) on a shape-churn recompile;
  * end-to-end: a ServeSession + lifetime walk under telemetry exports a
    snapshot that validates against tools/telemetry_schema.json.
"""
import json
import os
import sys
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (DEFAULT_BUCKETS, NULL_SPAN, OBS, MetricsRegistry,
                       RecompileError, RecompileSentinel, Telemetry,
                       diff_snapshots, parse_prometheus, snapshot,
                       to_prometheus, write_snapshot)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture
def obs_enabled():
    """Enable the process singleton for one test, then restore it to the
    pristine disabled state (other tests rely on disabled-by-default)."""
    OBS.reset()
    OBS.enable()
    yield OBS
    OBS.reset()
    OBS.disable()


# --------------------------------------------------------------------------- #
# registry semantics
# --------------------------------------------------------------------------- #
def test_counter_labels_and_aggregation():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", site="a").inc()
    reg.counter("req_total", site="a").inc(2)
    reg.counter("req_total", site="b").inc()
    series = reg.snapshot()["metrics"]["req_total"]["series"]
    by_site = {s["labels"]["site"]: s["value"] for s in series}
    assert by_site == {"a": 3.0, "b": 1.0}


def test_counter_rejects_negative():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.counter("n_total").inc(-1)


def test_gauge_set_and_add():
    reg = MetricsRegistry()
    g = reg.gauge("age_seconds", tag="t")
    g.set(5.0)
    g.add(2.0)
    g.set(3.5)
    (s,) = reg.snapshot()["metrics"]["age_seconds"]["series"]
    assert s["value"] == 3.5


def test_histogram_bucket_edges_inclusive():
    """Prometheus ``le`` semantics: a value equal to a bucket boundary
    counts into that bucket, not the next."""
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.1, 0.5, 1.0, 99.0):
        h.observe(v)
    (s,) = reg.snapshot()["metrics"]["lat_seconds"]["series"]
    assert s["bucket_counts"] == [2, 2, 1]       # le=0.1, le=1.0, +Inf
    assert s["count"] == 5
    assert s["min"] == 0.05 and s["max"] == 99.0
    assert s["sum"] == pytest.approx(100.65)


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x_total").inc()
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")


def test_thread_safety_under_pool():
    """N threads hammering one counter / one histogram series must lose
    no increments (one lock per metric)."""
    reg = MetricsRegistry()
    n_threads, per_thread = 8, 500

    def work(i):
        for _ in range(per_thread):
            reg.counter("hits_total", worker="shared").inc()
            reg.histogram("t_seconds", worker="shared").observe(1e-3)
        return i

    with ThreadPoolExecutor(n_threads) as pool:
        list(pool.map(work, range(n_threads)))
    met = reg.snapshot()["metrics"]
    (c,) = met["hits_total"]["series"]
    (h,) = met["t_seconds"]["series"]
    assert c["value"] == n_threads * per_thread
    assert h["count"] == n_threads * per_thread
    assert h["sum"] == pytest.approx(n_threads * per_thread * 1e-3)


# --------------------------------------------------------------------------- #
# disabled mode
# --------------------------------------------------------------------------- #
def test_disabled_span_is_shared_null():
    t = Telemetry(enabled=False)
    s = t.span("anything", site="x")
    assert s is NULL_SPAN
    with s:                                       # no-op context manager
        pass
    assert t.snapshot()["metrics"] == {}


def test_disabled_hot_path_records_nothing():
    """The instrumented executor path must leave the registry untouched
    while OBS is disabled (the hooks are one attribute check)."""
    assert not OBS.enabled                        # suite default
    OBS.reset()
    ex = _executor()
    x, w = _data()
    ex.calibrate(jax.random.PRNGKey(3), w, "quiet", n=4)
    np.asarray(ex.matmul(x, w, "quiet"))
    assert OBS.snapshot()["metrics"] == {}


# --------------------------------------------------------------------------- #
# exporters
# --------------------------------------------------------------------------- #
def _sample_registry():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests served", site="a#0").inc(3)
    reg.gauge("age_seconds", "drift age", tag='t"x').set(42.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.01, 0.1),
                      site="a#0")
    h.observe(0.005)
    h.observe(0.05)
    h.observe(5.0)
    return reg


def test_json_snapshot_roundtrip(tmp_path):
    reg = _sample_registry()
    path = tmp_path / "snap.json"
    write_snapshot(str(path), registry=reg)
    doc = json.loads(path.read_text())
    assert doc == reg.snapshot()
    assert doc["schema"] == 1


def test_prometheus_roundtrip():
    """JSON snapshot -> text exposition -> parsed samples, including a
    label value with an embedded quote and cumulative histogram series."""
    snap = _sample_registry().snapshot()
    text = to_prometheus(snap)
    vals = parse_prometheus(text)
    assert vals[("req_total", frozenset({("site", "a#0")}))] == 3.0
    assert vals[("age_seconds", frozenset({("tag", 't"x')}))] == 42.5
    buckets = {k: v for k, v in vals.items() if k[0] == "lat_seconds_bucket"}
    by_le = {dict(k[1])["le"]: v for k, v in buckets.items()}
    assert by_le == {"0.01": 1.0, "0.1": 2.0, "+Inf": 3.0}   # cumulative
    assert vals[("lat_seconds_count", frozenset({("site", "a#0")}))] == 3.0
    assert vals[("lat_seconds_sum",
                 frozenset({("site", "a#0")}))] == pytest.approx(5.055)


def test_diff_snapshots_zeroes_counters():
    reg = _sample_registry()
    base = reg.snapshot()
    d = diff_snapshots(base, reg.snapshot())
    assert d["diff"] is True
    (c,) = d["metrics"]["req_total"]["series"]
    assert c["value"] == 0.0
    (h,) = d["metrics"]["lat_seconds"]["series"]
    assert h["count"] == 0 and h["bucket_counts"] == [0, 0, 0]
    # gauges pass through as the later value
    (g,) = d["metrics"]["age_seconds"]["series"]
    assert g["value"] == 42.5


# --------------------------------------------------------------------------- #
# neutrality: telemetry on/off changes neither traces nor bits
# --------------------------------------------------------------------------- #
def _executor(backend="analytic"):
    from repro.configs.base import AnalogConfig
    from repro.configs.rram_ps32 import CASE_A
    from repro.core.analog import AnalogExecutor
    return AnalogExecutor(acfg=AnalogConfig(backend=backend), geom=CASE_A,
                          use_pallas=False)


def _data(K=70, N=8, B=4, seed=0):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (K, N)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, K)) * 0.5
    return x, w


def _exercise(ex, x, w):
    """A deploy -> calibrate -> matmul -> age sequence touching every
    instrumented analog path; returns (outputs, per-tag trace counts)."""
    from repro.nonideal import Scenario, scenario_at_age
    ys = []
    ex.calibrate(jax.random.PRNGKey(3), w, "par", n=4)
    ys.append(np.asarray(ex.matmul(x, w, "par")))
    sc = Scenario(name="par", prog_sigma=0.05)
    ex.deploy(scenario=sc, key=jax.random.PRNGKey(5))
    ys.append(np.asarray(ex.matmul(x, w, "par")))
    ex.deploy(scenario=scenario_at_age(sc, 3600.0))
    ys.append(np.asarray(ex.matmul(x, w, "par")))
    traces = {tag: ent[2]._cache_size() for tag, ent in ex._fns.items()}
    return ys, traces


def test_telemetry_is_trace_and_bit_neutral(obs_enabled):
    """The gate on the whole design: identical jit trace counts and
    bit-identical f32 outputs with telemetry on vs off."""
    x, w = _data()
    OBS.disable()
    ys_off, traces_off = _exercise(_executor(), x, w)
    assert OBS.snapshot()["metrics"] == {}        # really was off
    OBS.enable()
    ys_on, traces_on = _exercise(_executor(), x, w)
    assert traces_on == traces_off
    for a, b in zip(ys_off, ys_on):
        assert np.array_equal(a, b)
    # and the enabled run did record the instrumented path
    met = OBS.snapshot()["metrics"]
    assert "analog_plan_cache_total" in met
    assert "analog_matmul_calls_total" in met
    assert "analog_traces_total" in met
    assert "analog_calibration_residual" in met


def test_enabled_counters_match_ground_truth(obs_enabled):
    """analog_traces_total must agree with jit's own executable count."""
    x, w = _data()
    ex = _executor()
    for _ in range(3):                            # same shape: one trace
        np.asarray(ex.matmul(x, w, "ct"))
    met = OBS.snapshot()["metrics"]
    traced = sum(s["value"]
                 for s in met["analog_traces_total"]["series"]
                 if s["labels"]["tag"] == "ct")
    assert traced == ex._fns["ct"][2]._cache_size() == 1
    calls = sum(s["value"]
                for s in met["analog_matmul_calls_total"]["series"]
                if s["labels"]["tag"] == "ct")
    assert calls == 3


# --------------------------------------------------------------------------- #
# RecompileSentinel
# --------------------------------------------------------------------------- #
def test_sentinel_passes_compile_once_block():
    fn = jax.jit(lambda a: a * 2.0)
    x = jnp.ones((4, 4))
    with RecompileSentinel(fns=[fn], label="ok") as sent:
        for _ in range(5):
            fn(x).block_until_ready()
    assert sent.ok
    assert sent.new_counts == {"fn[0]": 1}


def test_sentinel_strict_raises_on_recompile():
    fn = jax.jit(lambda a: a * 2.0)
    with pytest.raises(RecompileError, match="fn\\[0\\]"):
        with RecompileSentinel(fns=[fn], label="churn"):
            fn(jnp.ones((2, 2)))
            fn(jnp.ones((3, 3)))                  # second shape: recompile
    # non-strict records the verdict instead of raising
    fn2 = jax.jit(lambda a: a + 1.0)
    with RecompileSentinel(fns=[fn2], strict=False) as sent:
        fn2(jnp.ones((2, 2)))
        fn2(jnp.ones((3, 3)))
    assert sent.ok is False
    assert sent.violations == {"fn[0]": 2}


def test_sentinel_watches_executor_tags_created_inside():
    x, w = _data()
    ex = _executor()
    with RecompileSentinel(executor=ex, label="exec") as sent:
        np.asarray(ex.matmul(x, w, "new_tag"))    # tag born in the block
    assert sent.ok
    assert sent.new_counts == {"executor.unified[new_tag]": 1}


def test_sentinel_records_outcome_metric(obs_enabled):
    fn = jax.jit(lambda a: a - 1.0)
    with RecompileSentinel(fns=[fn], strict=False, label="ci"):
        fn(jnp.ones((2,)))
        fn(jnp.ones((3,)))
    met = OBS.snapshot()["metrics"]
    (s,) = [r for r in met["obs_sentinel_checks_total"]["series"]
            if r["labels"]["label"] == "ci"]
    assert s["labels"]["outcome"] == "violation" and s["value"] == 1.0


# --------------------------------------------------------------------------- #
# end-to-end: serve + lifetime under telemetry, validated against schema
# --------------------------------------------------------------------------- #
def test_serve_snapshot_validates_against_schema(obs_enabled, tmp_path):
    """A short ServeSession + lifetime walk under telemetry must export
    a snapshot that passes the checked-in CI schema
    (tools/telemetry_schema.json) and carries the fleet health gauges."""
    import check_telemetry
    from repro.launch.serve import ServeSession
    from repro.nonideal import LifetimeScheduler, Scenario

    ex = _executor()
    sess = ServeSession("gemma3-1b", reduced=True, reduced_layers=2,
                        batch=2, prompt_len=8, gen=4, seed=0, executor=ex)
    with RecompileSentinel(session=sess, executor=ex, strict=False,
                           label="test-serve"):
        sess.calibrate(n=4)
        sess.generate()

    sched = LifetimeScheduler(ex, Scenario(name="fleet", prog_sigma=0.03,
                                           drift_nu=0.05),
                              timeline=(("1h", 3600.0),), calib_n=8)
    _, w = _data()
    sched.run(w, "fleet", _data()[0])

    path = tmp_path / "snap.json"
    write_snapshot(str(path))
    snap = json.loads(path.read_text())
    with open(os.path.join(REPO, "tools", "telemetry_schema.json")) as f:
        schema = json.load(f)
    errs = check_telemetry.check(snap, schema)
    assert not errs, "\n".join(errs)

    met = snap["metrics"]
    # per-site latency histograms with observations
    for name in ("serve_prefill_seconds", "serve_decode_seconds"):
        (s,) = met[name]["series"]
        assert s["count"] >= 1 and "#" in s["labels"]["site"]
    # cache hit/miss counters
    events = {s["labels"]["event"]
              for s in met["analog_plan_cache_total"]["series"]}
    assert "miss" in events and "hit" in events
    # fleet health gauges from the lifetime walk
    ages = {s["labels"]["tag"]: s["value"]
            for s in met["lifetime_drift_age_seconds"]["series"]}
    assert ages["fleet"] == 3600.0
    ev = {s["labels"]["event"]: s["value"]
          for s in met["lifetime_events_total"]["series"]}
    assert ev["deploy"] == 1 and ev["checkpoint"] == 1
    assert ev["recalibrate"] == 2                 # cold + 1h refit
    assert met["analog_calibration_residual"]["series"]
