"""host_gap_ms: idle time of the busiest device per traced sweep charged to a ``hooi.*`` span other than ``hooi.wait`` (ms): host work of the sweep that leaves the device idle."""

from bench import trace_spans

trace_spans.install()


def read(ctx):
    idle = (ctx["trace"] or {}).get("idle_by_span") or {}
    hooi = {k: v for k, v in idle.items() if k.startswith("hooi.")}
    sweeps = ctx["window"]["n_sweeps"]
    if not hooi or not sweeps:
        return None
    return 1e3 * sum(v for k, v in hooi.items() if k != "hooi.wait") / sweeps
