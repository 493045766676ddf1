"""Block-size autotuner for the serving kernels.

Every kernel wrapper used to hardcode its tiling (``block_m=128`` /
``block_n=256`` -- and the flash-attention exemplar this repo started
from still carries a literal ``# TODO: tune BLOCK_SIZE``).  This module
replaces the constants with a measured choice: on first use of a
(kernel, backend, dtype, shape) combination the candidate configs are
timed on dummy operands and the winner is cached

  * in-process (``_MEM``), so one sweep serves the whole run, and
  * on disk (``cache_dir()/autotune.json`` -- ``$REPRO_CACHE_DIR``,
    else ``$XDG_CACHE_HOME/repro``, else ``~/.cache/repro``; the file
    itself overridable with ``$REPRO_AUTOTUNE_CACHE``), so repeat runs
    skip the sweep entirely.

Every resolution is also counted into the telemetry registry when
enabled (``autotune_resolutions_total{kernel, source}``, plus a sweep
duration histogram and a cache-path info gauge -- docs/observability.md).

Sweeping is explicit opt-in off-TPU (``REPRO_AUTOTUNE=1``): candidates
are timed through real compiles, which is exactly right for a serving
deployment or a benchmark run and exactly wrong for a unit-test sweep.
With tuning disabled every call resolves to the caller's default, so
the kernels behave like the old hardcoded constants.

``best_config`` may be consulted from inside a ``jit`` trace: the key is
shape-derived (static under tracing).  Callers under a trace pass
``measure=None`` -- a sweep there would time the tracing of nested jits,
not the kernel -- so a traced resolution is a cache hit or the default.

Every resolution is recorded (``report()``) so benchmark runs can write
the chosen block sizes and the cache-hit status into their artifact
(BENCH_speed.json schema 2, docs/performance.md).
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax

from repro.obs import OBS

_MEM: Dict[str, dict] = {}
_REPORT: Dict[str, dict] = {}
_DISK_VERSION = 1


def cache_dir() -> str:
    """Root of the repro disk caches.  Resolution order:

      1. ``REPRO_CACHE_DIR``    -- explicit override (CI runners and
         multi-user hosts point this at a job-local scratch dir so
         concurrent runs never collide on one shared cache file);
      2. ``XDG_CACHE_HOME``/repro -- the XDG base-directory convention;
      3. ``~/.cache/repro``     -- the historical default.
    """
    env = os.environ.get("REPRO_CACHE_DIR")
    if env:
        return env
    xdg = os.environ.get("XDG_CACHE_HOME")
    base = xdg if xdg else os.path.join(os.path.expanduser("~"), ".cache")
    return os.path.join(base, "repro")


def cache_path() -> str:
    """Autotune disk-cache file (``REPRO_AUTOTUNE_CACHE`` overrides the
    whole path; otherwise it lives under ``cache_dir()``)."""
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(cache_dir(), "autotune.json")


def enabled() -> bool:
    """Whether cache misses sweep (else the caller's default is used).

    ``REPRO_AUTOTUNE=1``/``0`` forces it; unset, sweeping is on only
    where the kernels actually compile (TPU) -- interpret-mode timings
    would tune for the wrong executor.
    """
    env = os.environ.get("REPRO_AUTOTUNE")
    if env is not None:
        return env not in ("0", "false", "")
    return jax.default_backend() == "tpu"


def _load_disk() -> dict:
    try:
        with open(cache_path()) as f:
            doc = json.load(f)
        if isinstance(doc, dict) and doc.get("version") == _DISK_VERSION:
            return doc.get("entries", {})
    except (OSError, json.JSONDecodeError, ValueError):
        pass
    return {}


def _store_disk(key: str, cfg: dict) -> None:
    path = cache_path()
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        entries = _load_disk()
        entries[key] = cfg
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"version": _DISK_VERSION, "entries": entries}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass                      # cache is best-effort; in-process holds


def _key(kernel: str, key_parts: Sequence) -> str:
    return "|".join([kernel, jax.default_backend()]
                    + [str(p) for p in key_parts])


def _measure_median(measure: Callable[[dict], float], cfg: dict,
                    reps: int = 5) -> float:
    measure(cfg)                  # warmup: compile outside the timing
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        measure(cfg)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def best_config(kernel: str, key_parts: Sequence, candidates: List[dict],
                measure: Optional[Callable[[dict], float]], default: dict,
                ) -> dict:
    """Resolve the config for one (kernel, backend, shape) combination.

    ``measure(cfg)`` runs the kernel once under ``cfg`` (it is invoked
    repeatedly and timed here).  A candidate that raises -- a kernel the
    compiler refuses -- propagates: the candidate lists are meant to hold
    only configurations that compile, and a silently lost candidate would
    hide the failure.  With tuning disabled or no ``measure``, ``default``
    is returned unconditionally (and recorded as such).
    """
    key = _key(kernel, key_parts)
    if key in _MEM:
        _record(kernel, key, _MEM[key], "memory")
        return _MEM[key]
    disk = _load_disk()
    if key in disk:
        _MEM[key] = disk[key]
        _record(kernel, key, disk[key], "disk")
        return disk[key]
    if not enabled() or measure is None:
        _record(kernel, key, default, "default")
        return default
    t_sweep = time.perf_counter()
    best, best_t = default, float("inf")
    for cfg in candidates:
        t = _measure_median(measure, cfg)
        if t < best_t:
            best, best_t = cfg, t
    if OBS.enabled:
        OBS.histogram("autotune_sweep_seconds",
                      "wall-clock of one candidate sweep (compiles "
                      "included)", kernel=kernel).observe(
                          time.perf_counter() - t_sweep)
    _MEM[key] = best
    _store_disk(key, best)
    _record(kernel, key, best, "swept")
    return best


def _record(kernel: str, key: str, cfg: dict, source: str) -> None:
    _REPORT[kernel] = {"key": key, "config": dict(cfg), "source": source}
    if OBS.enabled:
        OBS.counter("autotune_resolutions_total",
                    "block-size resolutions per kernel and source "
                    "(memory/disk cache hit, fresh sweep, or the "
                    "caller's default)", kernel=kernel, source=source).inc()
        OBS.gauge("autotune_cache_path_info",
                  "constant 1; the label carries the active autotune "
                  "disk-cache path", path=cache_path()).set(1)


def report() -> Dict[str, dict]:
    """Last resolution per kernel this process: the chosen config and
    where it came from (``memory`` / ``disk`` / ``swept`` / ``default``).
    Benchmark runs persist this next to their timings (schema 2)."""
    return {k: dict(v) for k, v in _REPORT.items()}


def clear(memory: bool = True, disk: bool = False) -> None:
    """Test/bench hook: drop the in-process (and optionally disk) cache."""
    if memory:
        _MEM.clear()
        _REPORT.clear()
    if disk:
        try:
            os.remove(cache_path())
        except OSError:
            pass
