"""The program's spans and scopes in a profiler trace.

``bench/trace_reduce.py`` reduces a trace to device busy, idle and
collective time and names idle gaps by the Python call over them. This
module adds what the program's own instrumentation (``repro.tracing``)
makes readable, over the same window (the extent of the ``bench.call``
spans) on the same busiest device:

* ``op_self_s``: self time of every device op, ``{"<module>/<op>": s}``
  (``trace_reduce`` keeps the top ten);
* ``program_spans``: ``{name: [[start_ns, end_ns], ...]}`` of every program
  span (``hooi.*``, ``plan``, ``plan.*``, ``sched.*``), the name taken
  before any ``#`` (a profiler appends a span's attributes after it);
* ``idle_by_span``: the idle seconds of the busiest device, each charged
  to the innermost program span open over it, or to ``(outside)``.

``install()`` makes ``trace_reduce.reduce_file`` return these keys beside
its own, from one parse of the trace. The readers that need them call it
when they are loaded, which is before any run reduces its trace.
``scope_seconds`` charges ``op_self_s`` to the device scopes the program
names (``HooiExecutor.op_scopes``). Where the program has no spans or
scopes, the keys are empty and the readers return None.
"""

from __future__ import annotations

import re

from bench import trace_reduce

OUTSIDE = "(outside)"
PROGRAM_SPAN = re.compile(r"^(hooi\.\w+|plan|plan\.\w+|sched\.\w+)$")


def base_name(name: str) -> str:
    """A span's name without the attributes a profiler appends."""
    return name.split("#", 1)[0]


def program_spans(host: dict) -> dict:
    """``{name: [[start, end], ...]}`` of the program's spans, in order."""
    out: dict[str, list] = {}
    for evs in host.values():
        for name, s, e in evs:
            base = base_name(name)
            if PROGRAM_SPAN.match(base):
                out.setdefault(base, []).append([s, e])
    for ivs in out.values():
        ivs.sort()
    return out


def innermost_timeline(spans: dict, lo: float, hi: float) -> list:
    """[lo, hi] cut into ``(start, end, name)`` pieces, ``name`` being the
    innermost program span open over the piece (the latest started; of
    two started together, the shorter), or ``OUTSIDE``."""
    ivs = [(s, e, name) for name, lst in spans.items() for s, e in lst
           if e > lo and s < hi]
    cuts = sorted({lo, hi} | {x for s, e, _ in ivs for x in (s, e)
                              if lo < x < hi})
    out: list = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [(s, -e, name) for s, e, name in ivs if s <= a and e >= b]
        name = max(cover)[2] if cover else OUTSIDE
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def charge(intervals, timeline) -> dict:
    """Seconds of sorted, disjoint ``intervals`` (ns) per timeline name."""
    out: dict[str, float] = {}
    j = 0
    for s, e in intervals:
        while j < len(timeline) and timeline[j][1] <= s:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][0] < e:
            a, b, name = timeline[k]
            d = min(b, e) - max(a, s)
            if d > 0:
                out[name] = out.get(name, 0.0) + d * 1e-9
            k += 1
    return out


def reduce_spans(pd) -> dict:
    """``op_self_s``, ``program_spans`` and ``idle_by_span`` of a trace."""
    devices, host = trace_reduce._events(pd)
    if not devices:
        return {}
    calls = [(s, e) for evs in host.values() for name, s, e in evs
             if name == trace_reduce.CALL_SPAN]
    if calls:
        lo, hi = min(s for s, _ in calls), max(e for _, e in calls)
    else:
        every = [(s, e) for evs in devices.values() for _, s, e in evs]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    busy = {d: trace_reduce.union_intervals([(s, e) for _, s, e in evs],
                                            lo, hi)
            for d, evs in sorted(devices.items())}
    busiest = max(busy, key=lambda d: sum(e - s for s, e in busy[d]))
    edges = [lo] + [x for iv in busy[busiest] for x in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    spans = program_spans(host)
    return {
        "op_self_s": trace_reduce.self_times(devices[busiest], lo, hi),
        "program_spans": spans,
        "idle_by_span": charge(idle, innermost_timeline(spans, lo, hi)),
    }


def reduce_file(path: str, top: int = 10) -> dict:
    """``trace_reduce.reduce_profile`` and ``reduce_spans`` of one file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = trace_reduce.reduce_profile(pd, top=top)
    out.update(reduce_spans(pd))
    return out


def install() -> None:
    """Have ``trace_reduce.reduce_file`` return the keys of this module."""
    trace_reduce.reduce_file = reduce_file


def scope_seconds(ctx: dict) -> dict | None:
    """Device self seconds per program scope in the traced window,
    ``{"zbuild": s, "oracle": s, "comm": s}`` (0 for a scope no op ran
    under); None without a trace or without ``op_scopes`` in the program.
    """
    tr = ctx.get("trace") or {}
    ex = (ctx.get("setup") or {}).get("executor")
    if not tr.get("op_self_s") or not hasattr(ex, "op_scopes"):
        return None
    scopes = ex.op_scopes()
    out = {s: 0.0 for s in ("zbuild", "oracle", "comm")}
    for op, sec in tr["op_self_s"].items():
        if op in scopes:
            out[scopes[op]] += sec
    return out


def scope_ms_per_sweep(ctx: dict, scope: str) -> float | None:
    by_scope = scope_seconds(ctx)
    sweeps = ctx["window"]["n_sweeps"]
    if by_scope is None or not sweeps:
        return None
    return 1e3 * by_scope[scope] / sweeps
