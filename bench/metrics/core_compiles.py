"""core_compiles: XLA compilations charged to the span ``hooi.core`` (``DistHooiStats.compiles``), summed over the window's calls, per sweep."""


def read(ctx):
    stats, sweeps = ctx["stats"], ctx["window"]["n_sweeps"]
    counts = [getattr(st, "compiles", None) for st in stats]
    if not counts or any(c is None for c in counts) or not sweeps:
        return None
    return sum(c.get("hooi.core", 0) for c in counts) / sweeps
