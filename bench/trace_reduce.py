"""From a profiler trace to device busy time, kernel time and idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
the events the reduction needs as plain dicts: the device planes' op and
module lines, and the host spans the benchmark itself annotates (names
starting ``bench.``). ``reduce`` works on that list alone, so it can be
checked on a small recorded trace without a chip.

Times are in nanoseconds on the profiler's clock, which it shares between
the host and device planes. Everything is clipped to the ``bench.window``
host span: the measured window.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
HOST_PREFIX = "bench."
# ops that only hold other ops (a scan's loop); left out of the op table
CONTAINER = re.compile(r"^%(while|conditional|call)[.0-9]* ")


def trace_file(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        out[str(k)] = v if isinstance(v, (int, float)) else str(v)
    return out


def extract(path: str) -> list:
    """The events of one trace file the reduction reads."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if device and line.name in (OPS_LINE, MODULES_LINE):
                keep = lambda ev: True
            elif not device:
                keep = lambda ev: ev.name.startswith(HOST_PREFIX)
            else:
                continue
            for ev in line.events:
                if keep(ev):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name, "t0": float(ev.start_ns),
                                "dur": float(ev.duration_ns),
                                "stats": _stats(ev) if device else {}})
    return out


def _union(intervals, lo: float, hi: float) -> list:
    """Merged [t0, t1) intervals clipped to [lo, hi)."""
    cl = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                if b > lo and a < hi)
    merged: list = []
    for a, b in cl:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def op_name(name: str) -> str:
    """An HLO op's instruction name: the text before ' = '."""
    return name.split(" = ", 1)[0]


def reduce(events: list, kernels: dict | None = None) -> dict:
    """Busy and idle time of the devices in the window, time per op name,
    per module and per kernel (``kernels`` maps a label to a regular
    expression its op events match), and the idle time by what the host
    was doing (the innermost ``bench.*`` span over each gap). A kernel's
    ``count`` is its events in the window, summed over the devices,
    ``launches`` the same with a launch cut by the window's edge counted
    by the share inside it. A trace of several devices also gives each
    kernel's count per device (``plane_counts``: label -> plane ->
    count), since a sum would hide a device that lost its events."""
    win = [e for e in events if e["name"] == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} span")
    lo, hi = win[0]["t0"], win[0]["t0"] + win[0]["dur"]
    planes = sorted({e["plane"] for e in events
                     if e["line"] in (OPS_LINE, MODULES_LINE)})
    busy_by_plane, ops, modules = {}, {}, {}
    kern = {k: {"count": 0, "launches": 0.0, "seconds": 0.0}
            for k in (kernels or {})}
    per_plane = {k: dict.fromkeys(planes, 0) for k in kern}
    for pl in planes:
        pe = [e for e in events if e["plane"] == pl]
        op_ev = [e for e in pe if e["line"] == OPS_LINE]
        if not op_ev:
            op_ev = [e for e in pe if e["line"] == MODULES_LINE]
        busy_by_plane[pl] = _union(
            [(e["t0"], e["t0"] + e["dur"]) for e in op_ev], lo, hi)
        for e in pe:
            a, b = max(e["t0"], lo), min(e["t0"] + e["dur"], hi)
            if b <= a:
                continue
            s = (b - a) * 1e-9
            if e["line"] == MODULES_LINE:
                row = modules.setdefault(e["name"], [0, 0.0])
            elif CONTAINER.match(e["name"]):
                continue
            else:
                row = ops.setdefault(op_name(e["name"]), [0, 0.0])
                for label, pat in (kernels or {}).items():
                    if re.search(pat, e["name"]):
                        kern[label]["count"] += 1
                        per_plane[label][pl] += 1
                        kern[label]["launches"] += (
                            (b - a) / e["dur"] if e["dur"] > 0 else 1.0)
                        kern[label]["seconds"] += s
            row[0] += 1
            row[1] += s
    busy = [sum(b - a for a, b in iv) * 1e-9
            for iv in busy_by_plane.values()]
    busy_s = sum(busy) / len(busy) if busy else 0.0

    # idle gaps of the first device, by the host span over them
    host = [e for e in events if e["name"].startswith(HOST_PREFIX)
            and e["name"] != WINDOW]
    idle: dict = {}
    if planes:
        prev = lo
        for a, b in busy_by_plane[planes[0]] + [[hi, hi]]:
            if a > prev:
                label = _label(host, prev, a)
                idle[label] = idle.get(label, 0.0) + (a - prev) * 1e-9
            prev = max(prev, b)
    top = lambda d: sorted(([k, v[1] if isinstance(v, list) else v]
                            for k, v in d.items()),
                           key=lambda kv: -kv[1])
    out = {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "devices": len(planes),
        "ops": {k: {"count": v[0], "seconds": v[1]} for k, v in ops.items()},
        "modules": {k: {"count": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "kernels": kern,
        "breakdown": {"device_ops": top(ops)[:10],
                      "idle_gaps": top(idle)[:10]},
    }
    if len(planes) > 1:
        out["plane_counts"] = per_plane
    return out


def _label(host: list, a: float, b: float) -> str:
    """Name of the host span that covers most of [a, b); the shortest
    such span wins a tie, so the innermost annotation names the gap."""
    best, best_key = "host: no bench span", (0.0, 0.0)
    for e in host:
        ov = min(b, e["t0"] + e["dur"]) - max(a, e["t0"])
        if ov <= 0:
            continue
        key = (ov, -e["dur"])
        if key > best_key:
            best, best_key = e["name"], key
    return best
