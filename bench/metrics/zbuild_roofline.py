"""zbuild_roofline: the Z build's least time per sweep (bench/zbuild_counts.py) over the device self time of the program's scope ``zbuild`` per traced sweep, busiest device, in %."""

from bench import trace_spans, zbuild_counts

trace_spans.install()


def read(ctx):
    ms, peak = trace_spans.scope_ms_per_sweep(ctx, "zbuild"), ctx["peak"]
    if not ms or peak is None:
        return None
    least, _ = zbuild_counts.least_time_s(ctx["config"]["core_dims"],
                                          ctx["plan"].metrics.per_mode, peak)
    return 100.0 * least / (1e-3 * ms)
