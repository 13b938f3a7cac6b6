"""window_compiles: ``DistHooiStats.step_compilations`` summed over the window's calls."""


def read(ctx):
    return sum(st.step_compilations for st in ctx["stats"])
