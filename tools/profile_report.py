#!/usr/bin/env python
"""Profile reporter: a ``jax.profiler`` trace attributed to the serving
path's named scopes and spans.

A device op in the trace is named by its HLO instruction only; which
site and which part of the emulator call it belongs to is in the
instruction's ``op_name`` metadata, which the profiler keeps in the
trace's metadata plane (one HLO proto per module).  This tool joins the
two and reports, over a window:

  * per analog call site (the ``analog:<site>`` named scope,
    ``AnalogExecutor.matmul``): the kernel's device time
    (``emulator_block_unified``), the conductance relayout
    (``emu_layout_g``), the drive/constant/output relayouts
    (``emu_layout_io``) and the site's other ops;
  * the device time outside every site (digital model work);
  * each idle gap of the device longer than ``--gap-ms``, named by the
    innermost program span over its middle (spans recorded with
    ``OBS.enable(profiler=True)`` share the device ops' clock);
  * each program span's count and median;
  * the device time under each named scope given with ``--scope`` (for
    example ``moe_route`` and ``moe_experts``, ``models/moe.py``).

  PYTHONPATH=src python tools/profile_report.py TRACE.xplane.pb
      [--window SPAN] [--gap-ms 1] [--scope NAME ...] [--json]

The window is the extent of the spans named ``--window`` (default:
every device op's extent).  An op is attributed by its own
instruction's ``op_name``: a multi-output fusion carries one, its first
root's, so all of its time goes to that root's scope.
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
from typing import Dict, Iterator, List, Optional, Tuple

KERNEL = "emulator_block_unified"
LAYOUTS = ("emu_layout_g", "emu_layout_io")
SITE_PREFIX = "analog:"
# the program's spans are Prometheus-legal names (repro.obs.trace); the
# runtime's events and the Python tracer's ("$file.py:line fn") are not
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*\Z")


# --------------------------------------------------------------------------- #
# The metadata plane's HLO protos (protobuf wire format, no schema needed)
# --------------------------------------------------------------------------- #
def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if c < 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; length-delimited values as
    bytes."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _sub(b: bytes, num: int) -> List[bytes]:
    return [v for f, v in _fields(b) if f == num]


def op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{module key: {instruction name: op_name}}`` from the HLO protos
    of a ``.xplane.pb``.  The module key is the metadata entry's name,
    ``<module>(<program id>)``, as the device's module events and the
    host ops' stats name it."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):                        # XSpace.planes
        if b"/host:metadata" not in _sub(plane, 2):     # XPlane.name
            continue
        for entry in _sub(plane, 4):                    # event_metadata
            for meta in _sub(entry, 2):                 # XEventMetadata
                key = b"".join(_sub(meta, 2)).decode()
                names: Dict[str, str] = {}
                for stat in _sub(meta, 5):              # XStat
                    for hlo in _sub(stat, 6):           # bytes: HloProto
                        for module in _sub(hlo, 1):     # HloModuleProto
                            for comp in _sub(module, 3):
                                for ins in _sub(comp, 2):
                                    name = b"".join(_sub(ins, 1)).decode()
                                    md = _sub(ins, 7)   # OpMetadata
                                    op = b"".join(_sub(md[0], 2)) if md \
                                        else b""
                                    names[name] = op.decode()
                if names:
                    out[key] = names
    return out


# --------------------------------------------------------------------------- #
# Events
# --------------------------------------------------------------------------- #
class Profile:
    """Device ops (with their ``op_name``) and host events of one trace,
    in nanoseconds on the trace's clock."""

    def __init__(self, path: str, device: int = 0):
        from jax.profiler import ProfileData
        names = op_names(path)
        by_module = {k.split("(", 1)[0]: v for k, v in names.items()}
        self.ops: List[Tuple[str, int, int, str]] = []
        self.host: List[Tuple[str, int, int]] = []
        host_ops = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name == f"/device:TPU:{device}":
                lines = {ln.name: list(ln.events) for ln in plane.lines}
                mods = [(e.start_ns, e.end_ns, e.name)
                        for e in lines.get("XLA Modules", ())]
                for e in lines.get("XLA Ops", ()):
                    mod = next((m for s, t, m in mods
                                if s <= e.start_ns < t), "")
                    instr = e.name.split(" = ", 1)[0].lstrip("%")
                    table = names.get(mod) or by_module.get(
                        mod.split("(", 1)[0], {})
                    self.ops.append((instr, e.start_ns, e.end_ns,
                                     table.get(instr, "")))
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        st = dict(e.stats)
                        if "hlo_op" in st:              # a host device's op
                            key = f"{st.get('hlo_module')}" \
                                  f"({st.get('program_id')})"
                            table = names.get(key) or by_module.get(
                                str(st.get("hlo_module")), {})
                            instr = str(st["hlo_op"])
                            host_ops.append((instr, e.start_ns, e.end_ns,
                                             table.get(instr, "")))
                        else:
                            self.host.append((e.name, e.start_ns, e.end_ns))
        if not self.ops:
            self.ops = host_ops

    # -- the window ------------------------------------------------------ #
    def window(self, span: Optional[str] = None) -> Tuple[int, int]:
        ev = ([(s, e) for n, s, e in self.host if n == span] if span
              else [(s, e) for _, s, e, _ in self.ops])
        if not ev:
            raise ValueError(f"no {'span ' + span if span else 'device op'}"
                             " in the profile")
        return min(s for s, _ in ev), max(e for _, e in ev)

    def _in(self, window):
        t0, t1 = window
        return [(n, max(s, t0), min(e, t1), op) for n, s, e, op in self.ops
                if e > t0 and s < t1]

    # -- what the reports read ------------------------------------------ #
    def scope_time(self, scope: str, window) -> Tuple[float, int]:
        """Device seconds and count of the window's ops under the named
        scope ``scope`` (a component of their ``op_name``)."""
        ops = [(s, e) for _, s, e, op in self._in(window)
               if scope in op.split("/")]
        return sum(e - s for s, e in ops) * 1e-9, len(ops)

    def host_spans(self, name: str, window) -> List[Tuple[int, int]]:
        """A span's intervals, clipped to the window."""
        t0, t1 = window
        return [(max(s, t0), min(e, t1)) for n, s, e in self.host
                if n == name and e > t0 and s < t1]

    def spans(self) -> List[str]:
        """Names of the program spans in the trace."""
        return sorted({n for n, _, _ in self.host if PROGRAM_SPAN.match(n)})

    def idle_gaps(self, window, min_s: float) -> List[tuple]:
        """(start ns, seconds, innermost program span, innermost host
        event) over the middle of every device gap longer than
        ``min_s``."""
        t0, t1 = window
        busy: List[List[int]] = []
        for _, s, e, _ in sorted(self._in(window), key=lambda o: o[1]):
            if busy and s <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], e)
            else:
                busy.append([s, e])
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        out = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if (b - a) * 1e-9 <= min_s:
                continue
            mid = (a + b) / 2
            cover = sorted((e - s, n) for n, s, e in self.host
                           if s <= mid <= e)
            span = next((n for _, n in cover if PROGRAM_SPAN.match(n)), "")
            out.append((a, (b - a) * 1e-9, span,
                        cover[0][1] if cover else ""))
        return out


def _part(op: str) -> str:
    comps = op.split("/")
    if KERNEL in comps:
        return "kernel"
    return next((c for c in comps if c in LAYOUTS), "other")


def report(p: Profile, window, gap_s: float = 1e-3,
           scopes: Tuple[str, ...] = ()) -> dict:
    ops = p._in(window)
    busy = sum(e - s for _, s, e, _ in ops) * 1e-9
    sites: Dict[str, Dict[str, float]] = {}
    top: Dict[str, list] = {}
    for instr, s, e, op in ops:
        site = next((c[len(SITE_PREFIX):] for c in op.split("/")
                     if c.startswith(SITE_PREFIX)), None)
        part = _part(op) if site is not None else "digital"
        row = sites.setdefault(site or "", {})
        row[part] = row.get(part, 0.0) + (e - s) * 1e-9
        if part == "kernel":
            row["launches"] = row.get("launches", 0) + 1
        ent = top.setdefault(instr, [0.0, site, part, op])
        ent[0] += (e - s) * 1e-9
    span_ms = {}
    for name in p.spans():
        d = [(e - s) * 1e-6 for s, e in p.host_spans(name, window)]
        if d:
            span_ms[name] = {"count": len(d),
                             "median_ms": statistics.median(d)}
    return {
        "window_s": (window[1] - window[0]) * 1e-9, "device_ops_s": busy,
        "sites": sites,
        "top_ops": sorted(([n] + v for n, v in top.items()),
                          key=lambda r: -r[1])[:10],
        "idle_gaps": [list(g[1:]) for g in p.idle_gaps(window, gap_s)],
        "spans": span_ms,
        "scopes": {name: dict(zip(("s", "ops"), p.scope_time(name, window)))
                   for name in scopes},
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--window", default=None,
                    help="span whose extent is the window")
    ap.add_argument("--gap-ms", type=float, default=1.0)
    ap.add_argument("--device", type=int, default=0)
    ap.add_argument("--scope", action="append", default=[],
                    help="a named scope whose device time to report")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    p = Profile(args.path, args.device)
    rep = report(p, p.window(args.window), args.gap_ms * 1e-3,
                 tuple(args.scope))
    if args.json:
        print(json.dumps(rep, indent=1))
        return
    print(f"window {rep['window_s']:.6f} s, device ops "
          f"{rep['device_ops_s']:.6f} s")
    for site, row in sorted(rep["sites"].items()):
        print(f"  {('analog:' + site) if site else '(no site)':<40} "
              + ", ".join(f"{k} {v:.6f}" if isinstance(v, float)
                          else f"{k} {v}" for k, v in sorted(row.items())))
    print("top ops (s, site, part, op_name):")
    for name, sec, site, part, op in rep["top_ops"]:
        print(f"  {name:<40} {sec:.6f} {site or '-'} {part} {op[:80]}")
    print(f"idle gaps over {args.gap_ms} ms (s, innermost program span, "
          "innermost host event):")
    for sec, span, event in rep["idle_gaps"]:
        print(f"  {sec:.6f} {span or '-'} {event[:80]}")
    print("spans (count, median ms):")
    for name, d in rep["spans"].items():
        print(f"  {name:<32} {d['count']:>6} {d['median_ms']:.4f}")
    for name, d in rep["scopes"].items():
        print(f"scope {name}: {d['s']:.6f} s over {d['ops']} ops "
              f"({100 * d['s'] / max(rep['device_ops_s'], 1e-12):.3f}% of "
              "device time)")


if __name__ == "__main__":
    main()
