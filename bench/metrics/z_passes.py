"""z_passes: ``DistHooiStats.z_passes`` summed over modes (HBM passes over Z per sweep)."""


def read(ctx):
    stats = ctx["stats"]
    if not stats or not stats[-1].z_passes:
        return None
    return sum(stats[-1].z_passes.values())
