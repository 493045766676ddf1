"""The benchmark harness: one cell, one run, driven by data.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<mix>.json``);
the mix's ``kind`` names its driver (``drivers/<kind>.py``).  A per-layer
metric is ``metrics/<metric>.py``; a kernel's operation and byte count is
``kernels/<kernel>.py``; a cell's correctness limits are
``limits/<cell>.json``.  Nothing here names a cell, a configuration or a
metric: adding one is adding files.

A driver module provides::

    setup(ctx) -> state           # build, warm every shape, fill the engine
    step(ctx, state) -> dict      # one whole timed call: {"rows",
                                  # "launches", "ctx_sum"}
    check(ctx, state) -> dict     # host copies of what the window served,
                                  # taken before the program state is freed
    compare(ctx, data, controls) -> dict  # "program" and each control
                                  # precision -> {number name: value}
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(SystemExit):
    pass


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, by file (names may hold
    dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(workload: str, bench: Optional[dict] = None):
    """(cell, config file, traffic file, limits) of one workload."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    limits = load_json("limits", workload + ".json")
    return cell, conf, traffic, limits


def seed_key(seed: int):
    """A JAX key from a seed of up to 64 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


# --------------------------------------------------------------------------- #
# Configuration file -> the model block the harness and reference read
# --------------------------------------------------------------------------- #
def model_of(conf: dict) -> dict:
    """The published config keys (as run) in the harness's own names."""
    c, a = conf["config"], conf.get("assumed", {})
    heads = c["num_attention_heads"]
    m = {
        "num_layers": c["num_hidden_layers"],
        "d_model": c["hidden_size"],
        "num_heads": heads,
        "num_kv_heads": c["num_key_value_heads"],
        "head_dim": c.get("head_dim", c["hidden_size"] // heads),
        "d_ff": c["intermediate_size"],
        "vocab_size": c["vocab_size"],
        "rope_theta": float(c["rope_theta"]),
        "norm_eps": float(c.get("rms_norm_eps", 1e-6)),
    }
    return m


def arch_config(conf: dict):
    """The program's ``ArchConfig`` for a configuration file: the
    registry entry of ``conf["arch"]`` with the file's sizes."""
    from repro.configs import get_config
    m = model_of(conf)
    base = get_config(conf["arch"])
    kw = dict(name=conf["name"], num_layers=m["num_layers"],
              d_model=m["d_model"], num_heads=m["num_heads"],
              num_kv_heads=m["num_kv_heads"], head_dim=m["head_dim"],
              d_ff=m["d_ff"], vocab_size=m["vocab_size"],
              rope_base=m["rope_theta"])
    return dataclasses.replace(base, **kw)


@contextlib.contextmanager
def registered(cfg):
    """Answer the program's name lookup (``repro.configs.get_config``)
    with this process's ``ArchConfig``."""
    import repro.configs as rc
    orig = rc.get_config

    def get_config(name):
        return cfg if name == cfg.name else orig(name)

    rc.get_config = get_config
    try:
        yield
    finally:
        rc.get_config = orig


# --------------------------------------------------------------------------- #
# Weights, made from the seed on the device in one jitted call
# --------------------------------------------------------------------------- #
def make_weights(cfg, geometry: Optional[str], seed: int):
    """(model weights in bf16, Conv4Xbar params in f32 or None), drawn
    from the seed in the serving layout's shapes."""
    import jax
    import jax.numpy as jnp
    from repro.models.common import is_schema_leaf
    from repro.models.model import model_schema
    schema = {"model": model_schema(cfg)}
    if geometry:
        from repro.configs.rram_ps32 import BLOCKS
        from repro.core.conv4xbar import conv4xbar_schema
        schema["emulator"] = conv4xbar_schema(BLOCKS[geometry], n_periph=2)
    leaves, treedef = jax.tree_util.tree_flatten(schema,
                                                 is_leaf=is_schema_leaf)
    dts = jax.tree_util.tree_flatten(
        {k: jax.tree.map(lambda _: k == "model", v, is_leaf=is_schema_leaf)
         for k, v in schema.items()})[0]

    def draw(key):
        out = []
        for i, (p, bf16) in enumerate(zip(leaves, dts)):
            dt = jnp.bfloat16 if bf16 else jnp.float32
            if p.init == "zeros":
                out.append(jnp.zeros(p.shape, dt))
            elif p.init == "ones":
                out.append(jnp.ones(p.shape, dt))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, p.shape, jnp.float32)
                            * p.scale).astype(dt))
        return out

    vals = jax.jit(draw)(seed_key(seed))
    tree = jax.tree_util.tree_unflatten(treedef, vals)
    return tree["model"], tree.get("emulator")


# --------------------------------------------------------------------------- #
# Host spans and the compile listener
# --------------------------------------------------------------------------- #
class Spans:
    """Host spans of the harness, written into the profiler's trace when
    ``profile`` (the trace reduction reads them from there)."""

    def __init__(self, profile: bool = False):
        self.profile = profile

    def span(self, name: str):
        import jax
        return (jax.profiler.TraceAnnotation(name) if self.profile
                else contextlib.nullcontext())


class Compiles:
    """Backend compiles seen by ``jax.monitoring``, with their times."""

    def __init__(self):
        self.events: List[tuple] = []        # (end time, seconds)
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self):
        import jax

        def on_duration(event, duration, **_):
            if event.endswith("backend_compile_duration"):
                self.events.append((time.perf_counter(), duration))

        def on_event(event, **_):
            if "cache_hits" in event:
                self.cache_hits += 1
            elif "cache_misses" in event:
                self.cache_misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        return self

    def between(self, t0: float, t1: float):
        ev = [d for t, d in self.events if t0 <= t <= t1]
        return len(ev), sum(ev)


# --------------------------------------------------------------------------- #
# The run
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Ctx:
    """What a driver reads and builds for one run."""
    cell: dict
    conf: dict
    traffic: dict
    seed: int
    spans: Spans
    rng: Any
    device_kind: str
    model: dict = None
    arch: Any = None
    weights: Any = None
    eparams: Any = None
    executor: Any = None
    site_shapes: Any = ()
    phases: Dict[str, float] = dataclasses.field(default_factory=dict)

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        with self.spans.span("bench.setup." + name):
            yield
        self.phases[name] = self.phases.get(name, 0.0) \
            + time.perf_counter() - t0

    def make_session(self, batch: int, prompt_len: int, gen: int):
        """``ServeSession`` over this configuration, serving the
        benchmark's weights (its own draw is released at once)."""
        import jax
        from repro.launch.serve import ServeSession
        with registered(self.arch):
            sess = ServeSession(self.arch.name, reduced=False, batch=batch,
                                prompt_len=prompt_len, gen=gen,
                                seed=self.seed % (2 ** 31),
                                executor=self.executor)
        sess.params = None
        gc.collect()
        with self.phase("init"):
            if self.weights is None:
                geometry = (self.conf["crossbar"]["geometry"]
                            if self.executor is not None else None)
                self.weights, self.eparams = make_weights(
                    self.arch, geometry, self.seed)
                jax.block_until_ready(self.weights)
        sess.params = self.weights
        return sess

    def make_executor(self):
        """The crossbar executor of the configuration's mapping, at the
        traffic's corner (only the ideal corner is served here)."""
        from repro.configs.base import AnalogConfig
        from repro.configs.rram_ps32 import BLOCKS
        from repro.core.analog import AnalogExecutor
        xc = self.conf["crossbar"]
        if self.traffic.get("corner", "ideal") != "ideal":
            raise ValueError("only the ideal corner has a reference yet")
        geometry = xc["geometry"]
        self.weights, self.eparams = make_weights(self.arch, geometry,
                                                  self.seed)
        self.executor = AnalogExecutor(
            acfg=AnalogConfig(enabled=True, backend=xc["backend"],
                              layers=tuple(xc["layers"]),
                              wl_overdrive=xc.get("wl_overdrive", True)),
            geom=BLOCKS[geometry], emulator_params=self.eparams)
        return self.executor

    def free_program(self, state: dict):
        """Drop every reference the program holds on the device (the
        benchmark's own weights stay: the reference reads them)."""
        import jax
        self.executor = None
        state.clear()
        jax.clear_caches()
        gc.collect()


def device_info(n: int):
    import jax
    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, bench: Optional[dict] = None,
        conf_override: Optional[dict] = None, fault=None,
        controls: tuple = (), log=print) -> dict:
    """One run of one cell; returns the result object (the last line).

    ``controls``: names of ``sites.CONTROLS``, lower precisions at which
    the reference is also put in the program's place and judged by the
    same comparison and limits (by hand, ``bench/control.py``); each one's judgement is
    under ``result["controls"]``."""
    import numpy as np
    cell, conf, traffic, limits = cell_spec(workload, bench)
    if conf_override is not None:
        conf = conf_override
    import jax
    devs = jax.devices()
    if require_tpu:
        if devs[0].platform != "tpu":
            raise NoChip(f"bench: no TPU (JAX found {devs[0].platform}); "
                         "this benchmark never falls back to the CPU")
        if len(devs) < cell["chips"]:
            raise NoChip(f"bench: the cell needs {cell['chips']} chips, "
                         f"JAX found {len(devs)}")
        # every program in the checkout's cache, the eager ones that
        # materialize device states too
        jax.config.update("jax_compilation_cache_dir",
                          os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compiles = Compiles().install()

    spans = Spans(profile=trace)
    ctx = Ctx(cell=cell, conf=conf, traffic=traffic, seed=seed,
              spans=spans, rng=np.random.default_rng(seed),
              device_kind=devs[0].device_kind)
    ctx.model = model_of(conf)
    ctx.arch = arch_config(conf)
    driver = load_module("drivers", traffic["kind"])

    state = driver.setup(ctx)
    if fault is not None:
        fault(ctx, state)
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - t_start
    n_c, c_s = compiles.between(t_start, t_setup_end)
    log(f"setup: {setup_s:.3f} s; " + ", ".join(
        f"{k} {v:.3f} s" for k, v in ctx.phases.items())
        + f"; {n_c} compiles, {c_s:.3f} s compiling; persistent cache "
        f"hits {compiles.cache_hits}, misses {compiles.cache_misses}")

    if trace:
        import shutil
        tdir = os.path.join(TRACE_DIR, workload.replace("/", "_"))
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    calls = []
    t_win = time.perf_counter()
    while not calls or time.perf_counter() - t_win < seconds:
        t0 = time.perf_counter()
        with spans.span("bench.call"):
            rec = driver.step(ctx, state)
        calls.append(dict(rec, t0=t0, t1=time.perf_counter()))
    t_end = calls[-1]["t1"]
    if trace:
        jax.profiler.stop_trace()
    window_s = t_end - calls[0]["t0"]
    n_c, c_s = compiles.between(t_win, t_end)
    dev = device_info(cell["chips"])
    log("calls (s): " + " ".join(f"{c['t1'] - c['t0']:.4f}"
                                 for c in calls[:40]))
    log(f"window: {len(calls)} calls in {window_s:.6f} s; {n_c} compiles "
        f"({c_s:.3f} s) inside it; peak {dev['memory_peak_bytes']} B")

    result = {"correct": False, "attempted": len(calls), "failed": 0,
              "metrics": {}, "device": dev}
    ends = {m["name"]: m for m in benchmark_metrics(bench, "end_to_end",
                                                    workload)}
    per_layer = {m["name"]: m for m in benchmark_metrics(bench, "per_layer",
                                                         workload)}
    if trace:
        from bench import trace as tr
        reading = tr.read_profile(tdir, calls, ctx, log=log)
        shutil.rmtree(tdir, ignore_errors=True)
        dev["busy_s"] = reading.busy_s
        dev["window_s"] = reading.window_s
        for name, m in per_layer.items():
            val = load_module("metrics", name).read(reading)
            if val is not None:
                result["metrics"][name] = {"value": val, "unit": m["unit"]}
        result["breakdown"] = reading.breakdown()
    else:
        values = driver_metrics(calls, window_s, setup_s)
        for name, m in ends.items():
            if name in values:
                result["metrics"][name] = {"value": values[name],
                                           "unit": m["unit"]}

    # correctness: host copies of what was served, then the program's
    # state is freed and the reference runs in its place
    data = driver.check(ctx, state)
    ctx.free_program(state)
    t_ref = time.perf_counter()
    judged = driver.compare(ctx, data, tuple(controls))
    log(f"reference: {time.perf_counter() - t_ref:.3f} s")
    for name in controls:
        ok, compared = judge(judged[name], limits, lambda *a, **k: None)
        result.setdefault("controls", {})[name] = {
            "correct": ok, "checks": compared,
            "readings": unjudged(judged[name], limits)}
    ok, compared = judge(judged["program"], limits, log)
    result["readings"] = unjudged(judged["program"], limits)
    result["checks"] = compared          # the last key of the line
    result["correct"] = ok
    return result


def unjudged(checks: dict, limits: dict) -> dict:
    return {k: v for k, v in checks.items() if k not in limits}


def judge(checks: dict, limits: dict, log):
    """(correct, {name: {value, limit}}): every number a limit names is
    finite and within it; the others are printed beside them."""
    import numpy as np
    ok, compared = True, {}
    for name, val in checks.items():
        if name not in limits:
            log(f"reading {name}: {val!r}", file=sys.stderr)
    for name, lim in limits.items():
        val = checks[name]
        good = bool(np.isfinite(val)) and val <= lim
        ok &= good
        compared[name] = {"value": val, "limit": lim}
        log(f"check {name}: {val!r} limit {lim!r} "
            f"{'ok' if good else 'FAILED'}", file=sys.stderr)
    return bool(ok), compared


def benchmark_metrics(bench: Optional[dict], kind: str, workload: str):
    bench = bench or benchmark()
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def driver_metrics(calls: List[dict], window_s: float, setup_s: float):
    """End-to-end numbers of a window of whole calls."""
    return {"setup_s": setup_s,
            "emulated_tok_s": sum(c["rows"] for c in calls) / window_s}
