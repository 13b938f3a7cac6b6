"""sweep_roofline: least time per sweep (bench/counts.py) over device-busy time per sweep, in %."""

from bench import counts


def read(ctx):
    tr, sweeps, peak = ctx["trace"], ctx["window"]["n_sweeps"], ctx["peak"]
    if not tr or not tr.get("devices") or not sweeps or peak is None:
        return None
    busy = tr["busiest_busy_s"] / sweeps
    if busy <= 0:
        return None
    least, _ = counts.least_time_s(ctx["config"]["core_dims"],
                                   ctx["plan"].metrics.per_mode, peak)
    return 100.0 * least / busy
