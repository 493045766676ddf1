"""The paper's headline claim: emulation is 'incomparably' faster than the
circuit simulator. Times one computing-block batch through:
  circuit   -- Newton-Raphson solver (SPICE stand-in)
  analytic  -- expert analytical model
  emulator  -- Conv4Xbar (paper conv path, fused path)
and a system-level figure: one AnalogMatmul (K=512, N=32) per backend.

Besides the CSV lines, every run appends a machine-readable entry to
``BENCH_speed.json`` at the repo root (see docs/performance.md for the
schema) so the perf trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import QUICK, get_conditioned_emulator, get_emulator, \
    timed
from repro.configs.base import AnalogConfig
from repro.configs.rram_ps32 import CASE_A, EmulatorTrainConfig
from repro.core import conv4xbar
from repro.core.analog import AnalogExecutor
from repro.core.analytic import analytic_block_response
from repro.core.circuit import CircuitParams, block_response
from repro.core.emulator import normalize_features, sample_block_inputs
from repro.obs import OBS

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_speed.json")

# tiny protocol for CI smoke runs: exercises every code path, proves nothing
# about emulator quality
SMOKE = EmulatorTrainConfig(n_train=512, n_test=128, epochs=2, lr=2e-3,
                            lr_halve_at=(), batch_size=256)


def _pallas_backend() -> str:
    """How the Pallas kernel executes on this backend (the run row's
    ``pallas`` field)."""
    return "tpu" if jax.default_backend() == "tpu" else "interp"


def run(batch: int = 2048, seed: int = 0, tcfg=QUICK, iters: int = 3,
        with_circuit: bool = True):
    # telemetry rides along so the cache-hit counters land in the run row
    # (schema 3)
    OBS.enable()
    geom, acfg, cp = CASE_A, AnalogConfig(), CircuitParams()
    res = get_emulator(geom.name, tcfg, seed)
    key = jax.random.PRNGKey(seed)
    x, periph = sample_block_inputs(key, batch, geom, acfg)
    xn = normalize_features(x, acfg)

    fns = {
        "analytic": jax.jit(lambda a, p: analytic_block_response(a, cp, p)),
        "emulator_conv": jax.jit(
            lambda a, p: conv4xbar.apply(res.params, a, p)),
        "emulator_fused": jax.jit(
            lambda a, p: conv4xbar.apply_fused(res.params, a, p)),
    }
    if with_circuit:
        fns["circuit"] = jax.jit(lambda a, p: block_response(a, cp, p))
    rows = {}
    for name, fn in fns.items():
        arg = x if name in ("circuit", "analytic") else xn
        dt, _ = timed(fn, arg, periph, iters=iters)
        rows[name] = dt / batch * 1e6          # us per block

    # system level: one matmul through the executor
    w = jax.random.normal(key, (512, 32)) * 0.2
    xin = jax.random.normal(jax.random.fold_in(key, 1), (16, 512)) * 0.5
    sys_rows = {}
    backends = ("circuit", "analytic", "emulator") if with_circuit else \
        ("analytic", "emulator")
    for backend in backends:
        ex = AnalogExecutor(
            acfg=dataclasses.replace(acfg, backend=backend), geom=geom,
            cp=cp, emulator_params=res.params)
        fn = jax.jit(lambda a: ex.matmul(a, w, "bench"))
        dt, _ = timed(fn, xin, iters=iters)
        sys_rows[backend] = dt * 1e6
    # scenario serving overhead: same matmul through the per-tag unified
    # forward ("stressed" corner), timed as the eager dispatch (read noise
    # redrawn per call, in-trace fast-path precompute).  Worst case: a serve
    # loop that jits an enclosing step bakes the perturbation at trace time
    # and pays ~the plain emulator row instead.
    from repro.nonideal import get_scenario
    ex_sc = AnalogExecutor(
        acfg=dataclasses.replace(acfg, backend="emulator"), geom=geom,
        cp=cp, emulator_params=res.params)
    ex_sc.deploy(scenario=get_scenario("stressed"),
                 key=jax.random.PRNGKey(seed))
    dt, _ = timed(lambda a: ex_sc.matmul(a, w, "bench"), xin, iters=iters)
    sys_rows["emulator_nonideal"] = dt * 1e6
    # unified cache at the IDEAL deployment: the eager per-tag dispatch
    # with the whole DeploymentState as ONE traced argument -- the single
    # jit-cache family that replaced the plain/calibration/scenario trio.
    # Gated below within 5% of the fast-path rows it unified.
    ex_u = AnalogExecutor(
        acfg=dataclasses.replace(acfg, backend="emulator"), geom=geom,
        cp=cp, emulator_params=res.params)
    dt, _ = timed(lambda a: ex_u.matmul(a, w, "bench"), xin, iters=iters)
    sys_rows["emulator_unified"] = dt * 1e6
    # scenario-conditioned emulator on the PLAIN fast path: the ideal
    # (all-zero) feature block folds into the cached weights, so the
    # conditioning overhead should be within noise of the emulator row
    cond = get_conditioned_emulator(geom.name, tcfg, seed)
    ex_cd = AnalogExecutor(
        acfg=dataclasses.replace(acfg, backend="emulator"), geom=geom,
        cp=cp, emulator_params=cond.params)
    fn = jax.jit(lambda a: ex_cd.matmul(a, w, "bench"))
    dt, _ = timed(fn, xin, iters=iters)
    sys_rows["emulator_conditioned"] = dt * 1e6
    # the unified serving dispatcher, jitted: ONE fused pallas_call per
    # matmul on TPU (both rails + both GEMM stages + scenario epilogue);
    # on non-TPU hosts the dispatcher's same-math XLA schedule runs
    # instead (interpret-mode kernel timings would benchmark the
    # interpreter, not the kernel), so there the row tracks the jitted
    # fast path and the gate is a no-regression check on the dispatcher.
    ex_pl = AnalogExecutor(
        acfg=dataclasses.replace(acfg, backend="emulator"), geom=geom,
        cp=cp, emulator_params=res.params)
    fn = jax.jit(lambda a: ex_pl.matmul(a, w, "bench"))
    dt, _ = timed(fn, xin, iters=iters)
    sys_rows["emulator_pallas_unified"] = dt * 1e6
    dt, _ = timed(jax.jit(lambda a: a @ w), xin, iters=iters)
    sys_rows["digital"] = dt * 1e6
    # tensor-parallel serving row (docs/parallel.md): the same matmul
    # through a (2, 4) data x model mesh.  Only measurable when the
    # process has >= 8 devices (the CI multidevice-smoke job forces
    # XLA_FLAGS=--xla_force_host_platform_device_count=8).  NOTE: forced
    # host devices multiplex the host's physical cores -- on a
    # single-core host the row records the partitioning OVERHEAD, not a
    # speedup; a real >= 1.5x needs >= 8 real cores/devices
    # (docs/performance.md).
    if len(jax.devices()) >= 8:
        from repro.parallel.sharding import serve_mesh
        ex_sh = AnalogExecutor(
            acfg=dataclasses.replace(acfg, backend="emulator"), geom=geom,
            cp=cp, emulator_params=res.params, mesh=serve_mesh(2, 4))
        fn = jax.jit(lambda a: ex_sh.matmul(a, w, "bench"))
        dt, _ = timed(fn, xin, iters=iters)
        sys_rows["emulator_sharded"] = dt * 1e6
    return rows, sys_rows


def _obs_summary() -> dict:
    """Counter totals worth tracking per run: executor cache hit/miss
    counts, folded out of the full telemetry snapshot
    (docs/observability.md)."""
    met = OBS.snapshot()["metrics"]

    def by_label(name: str, label: str) -> dict:
        out: dict = {}
        for s in met.get(name, {}).get("series", []):
            k = s["labels"].get(label, "?")
            out[k] = out.get(k, 0) + int(s["value"])
        return out

    return {"plan_cache": by_label("analog_plan_cache_total", "event"),
            "state_cache": by_label("analog_state_cache_total", "event")}


def write_json(rows, sys_rows, label: str, path: str = BENCH_JSON):
    """Append this run to the perf-trajectory file (schema v3: each run
    row also records the telemetry counter summary under ``obs``; see
    docs/performance.md)."""
    doc = {"schema": 3, "unit_block": "us_per_block",
           "unit_matmul": "us_per_matmul_512x32_b16", "runs": []}
    if os.path.exists(path):
        try:
            with open(path) as f:
                prev = json.load(f)
            if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
                doc["runs"] = prev["runs"]
        except (json.JSONDecodeError, OSError):
            pass
    doc["runs"].append({
        "label": label,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "jax_backend": jax.default_backend(),
        "cpus": os.cpu_count(),
        "pallas": _pallas_backend(),
        "block_us": {k: round(v, 3) for k, v in rows.items()},
        "matmul_us": {k: round(v, 1) for k, v in sys_rows.items()},
        "obs": _obs_summary(),
    })
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    return path


def main(csv=True, quick: bool = False, label: str | None = None):
    if quick:
        rows, sys_rows = run(batch=256, tcfg=SMOKE, iters=2,
                             with_circuit=False)
    else:
        rows, sys_rows = run()
    # unified-cache gate: the ONE per-tag forward (DeploymentState as a
    # single traced arg) must stay within 5% of the fast-path rows it
    # unified -- the jit-baked plain row and the traced scenario row
    ref = max(sys_rows["emulator"], sys_rows["emulator_nonideal"])
    unified_ok = sys_rows["emulator_unified"] <= 1.05 * ref
    # fused-kernel gate: the jitted unified dispatcher must never regress
    # past the eager unified forward it accelerates
    pallas_ok = (sys_rows["emulator_pallas_unified"]
                 <= 1.0 * sys_rows["emulator_unified"])
    if csv:
        for k, v in rows.items():
            print(f"speed_block_{k},{v:.2f},us_per_block")
        for k, v in sys_rows.items():
            print(f"speed_matmul_{k},{v:.1f},us_per_matmul_512x32_b16")
        print(f"speed_unified_within_5pct,{int(unified_ok)},bool")
        print(f"speed_pallas_unified_no_regress,{int(pallas_ok)},bool")
        if "circuit" in rows:
            speedup = rows["circuit"] / rows["emulator_fused"]
            print(f"speed_emulator_speedup,{speedup:.1f},circuit/emulator_fused"
                  f" (CPU; paper's claim is orders-of-magnitude vs SPICE)")
    path = write_json(rows, sys_rows,
                      label or ("quick" if quick else "full"))
    print(f"bench_json,{os.path.abspath(path)},appended")
    if not unified_ok:
        raise SystemExit(
            f"unified-cache overhead gate violated: emulator_unified "
            f"{sys_rows['emulator_unified']:.1f} us > 1.05 x "
            f"max(emulator, emulator_nonideal) = {1.05 * ref:.1f} us")
    if not pallas_ok:
        raise SystemExit(
            f"fused-kernel gate violated: emulator_pallas_unified "
            f"{sys_rows['emulator_pallas_unified']:.1f} us > 1.0 x "
            f"emulator_unified = {sys_rows['emulator_unified']:.1f} us")
    return rows, sys_rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: tiny emulator, no circuit rows")
    ap.add_argument("--label", default=None,
                    help="label recorded in BENCH_speed.json")
    args = ap.parse_args()
    main(quick=args.quick, label=args.label)
