"""comm_ms: device self time of the ops in the program's scope ``comm`` per traced sweep, busiest device (ms)."""

from bench import trace_spans

trace_spans.install()


def read(ctx):
    return trace_spans.scope_ms_per_sweep(ctx, "comm")
