"""core_ms: device self time of the ops in the program's scope ``core`` (the core executables, ``HooiExecutor.core_ops``) per traced sweep, busiest device (ms)."""

from bench import trace_spans

trace_spans.install()


def read(ctx):
    tr = ctx.get("trace") or {}
    ex = (ctx.get("setup") or {}).get("executor")
    sweeps = ctx["window"]["n_sweeps"]
    if not tr.get("op_self_s") or not hasattr(ex, "core_ops") or not sweeps:
        return None
    ops = ex.core_ops()
    return 1e3 * sum(s for op, s in tr["op_self_s"].items()
                     if op in ops) / sweeps
