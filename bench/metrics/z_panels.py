"""z_panels: column panels the windowed Z build launched (``DistHooiStats.z_panels``), summed over the window's calls, per sweep."""


def read(ctx):
    stats, sweeps = ctx["stats"], ctx["window"]["n_sweeps"]
    panels = [getattr(st, "z_panels", None) for st in stats or ()]
    if not panels or any(p is None for p in panels) or not sweeps:
        return None
    return sum(panels) / sweeps
