"""plan_s: host wall seconds of the set-up's ``plan()`` call (plan cache cleared)."""


def read(ctx):
    return ctx["setup"]["plan_s"]
