"""sweep_s: the window's wall seconds over all sweeps completed in it."""


def read(ctx):
    win = ctx["window"]
    return win["window_s"] / win["n_sweeps"] if win["n_sweeps"] else None
