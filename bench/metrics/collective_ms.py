"""collective_ms: device ms per sweep in collective operations, on the busiest device."""


def read(ctx):
    tr, sweeps = ctx["trace"], ctx["window"]["n_sweeps"]
    if not tr or not tr.get("devices") or not sweeps:
        return None
    coll = tr["busiest_collective_s"]
    return 1e3 * coll / sweeps if coll > 0 else None
