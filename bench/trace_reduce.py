"""Reduction of a profiler trace (``.xplane.pb``) to device time.

Reads the trace with ``jax.profiler.ProfileData`` and nothing of the
program. Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run. Host planes hold the benchmark's own
``TraceAnnotation`` spans (``bench.*``) and, with the Python tracer on,
one event per Python call.

The window is the extent of the ``bench.call`` spans (the traced calls),
or of the whole trace where there are none. Within it, per device:

* ``busy_s``: the union of operation intervals;
* ``collective_s``: the summed durations of collective operations;

and on the busiest device the operations by self time (an XLA ``while``
holds its body's operations as events of their own), and the idle gaps,
each named by the innermost Python call that covers its midpoint and its
caller: what the host was doing meanwhile.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
PYTHON_LINE = "python"
CALL_SPAN = "bench.call"
COLLECTIVE = re.compile(
    r"^(.*/)?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast|send|recv)\b")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``."""
    return hlo.split(" =", 1)[0].lstrip("%")


def _events(pd):
    """(device ops by device id, host events by line) as (name, start, end).

    A device op is named ``<module>/<instruction>``, its module being the
    ``XLA Modules`` event that holds it, without the trailing hash.
    """
    devices: dict[int, list] = {}
    host: dict[str, list] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((float(ev.start_ns),
                           float(ev.start_ns) + float(ev.duration_ns),
                           ev.name.split("(", 1)[0])
                          for ev in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            starts = [x[0] for x in mods]
            evs = devices.setdefault(int(m.group(1)), [])
            for ev in lines[OPS_LINE].events if OPS_LINE in lines else ():
                s = float(ev.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] + "/" if i >= 0 and mods[i][1] >= s else ""
                evs.append((mod + op_name(ev.name), s,
                            s + float(ev.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(
                    (ev.name, float(ev.start_ns),
                     float(ev.start_ns) + float(ev.duration_ns))
                    for ev in line.events)
    return devices, host


def self_times(evs, lo: float, hi: float) -> dict:
    """Seconds per op name within [lo, hi], less the ops nested inside."""
    clipped = sorted(((max(s, lo), min(e, hi), name) for name, s, e in evs
                      if min(e, hi) > max(s, lo)),
                     key=lambda x: (x[0], -x[1]))
    own = [e - s for s, e, _ in clipped]
    stack: list = []
    for i, (s, e, _) in enumerate(clipped):
        while stack and clipped[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    out: dict[str, float] = {}
    for (_, _, name), d in zip(clipped, own):
        out[name] = out.get(name, 0.0) + d * 1e-9
    return out


def union_intervals(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi], in order."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _gap_name(host: dict, t: float) -> str:
    """What the host was doing at time ``t``: the innermost Python call
    covering it and its caller (or the innermost host event of any line
    where no Python call covers it)."""
    lines = [host.get(PYTHON_LINE, [])] + [
        evs for name, evs in host.items() if name != PYTHON_LINE]
    for evs in lines:
        cover = sorted((s, -e, name) for name, s, e in evs if s <= t < e)
        if cover:
            names = [name for _, _, name in cover[-2:]]
            return " > ".join(names)
    return "(no host event)"


def reduce_profile(pd, top: int = 10) -> dict:
    """Device busy, idle and collective time of a ``ProfileData``."""
    devices, host = _events(pd)
    if not devices:
        return {"devices": {}}
    calls = [(s, e) for evs in host.values() for name, s, e in evs
             if name == CALL_SPAN]
    if calls:
        lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    else:
        spans = [(s, e) for evs in devices.values() for _, s, e in evs]
        lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    per_dev = {}
    for dev, evs in sorted(devices.items()):
        busy = union_intervals([(s, e) for _, s, e in evs], lo, hi)
        coll = sum(min(e, hi) - max(s, lo) for name, s, e in evs
                   if COLLECTIVE.match(name) and min(e, hi) > max(s, lo))
        per_dev[dev] = {"busy": busy,
                        "busy_s": sum(e - s for s, e in busy) * 1e-9,
                        "collective_s": coll * 1e-9}
    busiest = max(per_dev, key=lambda d: per_dev[d]["busy_s"])
    busy = per_dev[busiest]["busy"]
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                   if e > s), reverse=True)
    window_s = (hi - lo) * 1e-9
    ops = sorted(self_times(devices[busiest], lo, hi).items(),
                 key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "n_calls": len(calls),
        "busiest": busiest,
        "devices": {d: {k: v for k, v in x.items() if k != "busy"}
                    for d, x in per_dev.items()},
        "busy_s_mean": sum(x["busy_s"] for x in per_dev.values())
        / len(per_dev),
        "busiest_busy_s": per_dev[busiest]["busy_s"],
        "busiest_collective_s": per_dev[busiest]["collective_s"],
        "idle_share": 1.0 - per_dev[busiest]["busy_s"] / window_s
        if window_s > 0 else None,
        "device_ops": [[name, sec] for name, sec in ops[:top]],
        "idle_gaps": [[_gap_name(host, 0.5 * (s + e)), d * 1e-9]
                      for d, s, e in gaps[:top]],
    }


def reduce_file(path: str, top: int = 10) -> dict:
    """``reduce_profile`` of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), top=top)
