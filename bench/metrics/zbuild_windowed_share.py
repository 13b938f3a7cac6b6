"""zbuild_windowed_share: share of the window's mode steps whose Z build ran the windowed kernel (``DistHooiStats.z_build``), %."""


def read(ctx):
    builds = [getattr(st, "z_build", None) for st in ctx["stats"] or ()]
    if not builds or any(b is None for b in builds):
        return None
    steps = sum(len(b) for b in builds)
    return 100.0 * sum(v == "windowed" for b in builds for v in b.values()) / steps
