"""Reduction of one traced window to device numbers.

The window is that of the harness's ``bench.call`` spans, read from the
profile itself, so host and device events share the trace's clock.
Device operations are the events of each used chip's ``XLA Ops`` line
(on a host with no accelerator, the host events that carry an
``hlo_op``, which only the reduction's own test uses).  Busy time is
the union of their intervals in the window; an idle gap is named by the
shortest host event that spans its middle.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Tuple

from bench import harness as H


def _stats(ev) -> Dict[str, object]:
    try:
        return dict(ev.stats)
    except Exception:                                   # pragma: no cover
        return {}


def load_events(path: str, n_chips: int = 1):
    """(device ops per chip, host events) of one ``.xplane.pb``: each
    event as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev: Dict[str, list] = {}
    host, host_ops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            if int(plane.name.rsplit(":", 1)[1]) >= n_chips:
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                dev[plane.name] = [(e.name, e.start_ns, e.end_ns, _stats(e))
                                   for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    st = _stats(e)
                    rec = (e.name, e.start_ns, e.end_ns, st)
                    (host_ops if "hlo_op" in st else host).append(rec)
    if not dev and host_ops:
        dev["/host:CPU"] = host_ops
    return dev, host


def short_name(name: str) -> str:
    """A device op's name without its HLO text: ``%fusion.50`` and the
    result's shape, or the custom call's target."""
    head, _, rest = name.partition(" = ")
    if "custom_call_target=" in rest:
        target = rest.split("custom_call_target=", 1)[1].split(",", 1)[0]
        return f"{head} {target.strip(chr(34))}"
    return f"{head} {rest.split(' ', 1)[0]}"[:120] if rest else name[:120]


def merge(intervals: List[Tuple[float, float]]):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


class Reading:
    """What the per-layer metric readers read."""

    def __init__(self, dev, host, calls, ctx):
        self.ctx, self.calls = ctx, calls
        marks = [(s, e) for n, s, e, _ in host if n == "bench.call"]
        if not marks:
            raise RuntimeError("the profile holds no bench.call span")
        self.t0 = min(s for s, _ in marks)
        self.t1 = max(e for _, e in marks)
        self.window_s = (self.t1 - self.t0) * 1e-9
        self.host = host
        self.ops = {d: [(n, max(s, self.t0), min(e, self.t1), st)
                        for n, s, e, st in evs
                        if e > self.t0 and s < self.t1]
                    for d, evs in dev.items()}
        busy = [merge([(s, e) for _, s, e, _ in evs])
                for evs in self.ops.values()]
        self.busy = busy
        self.busy_s = (sum(sum(b - a for a, b in m) for m in busy)
                       / max(1, len(busy)) * 1e-9)
        self._peaks = None

    # -- device time ----------------------------------------------------- #
    def kernel_time(self, names) -> Tuple[float, int]:
        """Seconds and count of the ops whose name or stats hold one of
        ``names``, summed over the used chips, averaged per chip."""
        tot, n = 0.0, 0
        for evs in self.ops.values():
            for name, s, e, st in evs:
                text = name + " " + " ".join(str(v) for v in st.values())
                if any(k in text for k in names):
                    tot += (e - s) * 1e-9
                    n += 1
        k = max(1, len(self.ops))
        return tot / k, n // k

    def peaks(self) -> dict:
        if self._peaks is None:
            table = H.load_json("peaks.json")["devices"]
            kind = self.ctx.device_kind
            if kind not in table:
                raise KeyError(f"no peaks for device {kind!r} in peaks.json")
            self._peaks = table[kind]
        return self._peaks

    def kernel(self, name: str):
        return H.load_module("kernels", name)

    def launches(self):
        return [l for c in self.calls for l in c.get("launches", ())]

    # -- the breakdown line --------------------------------------------- #
    def breakdown(self) -> dict:
        agg: Dict[str, float] = {}
        for evs in self.ops.values():
            for name, s, e, _ in evs:
                key = short_name(name)
                agg[key] = agg.get(key, 0.0) + (e - s) * 1e-9
        k = max(1, len(self.ops))
        ops = sorted(((n, v / k) for n, v in agg.items()),
                     key=lambda x: -x[1])[:10]
        gaps = []
        for m in self.busy[:1]:
            edges = [self.t0] + [x for ab in m for x in ab] + [self.t1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((a, b))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
        named = []
        for a, b in gaps:
            mid = (a + b) / 2
            cover = [(e - s, n) for n, s, e, _ in self.host
                     if s <= mid <= e and e > s]
            named.append([min(cover)[1] if cover else "host",
                          (b - a) * 1e-9])
        return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}


def read_profile(tdir: str, calls, ctx, log=print) -> Reading:
    paths = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no profile written under {tdir}")
    dev, host = load_events(sorted(paths)[-1], ctx.cell["chips"])
    r = Reading(dev, host, calls, ctx)
    for evs in list(r.ops.values())[:1]:
        for name, _, _, st in evs:
            if "custom-call" in name or "custom_call" in name:
                log("trace: a custom call's stats: " + json.dumps(
                    {k: str(v)[:160] for k, v in st.items()}))
                break
    log(f"trace: window {r.window_s:.6f} s, busy {r.busy_s:.6f} s, "
        f"{sum(len(v) for v in r.ops.values())} device ops; top "
        + json.dumps(r.breakdown()["device_ops"][:5]))
    return r
