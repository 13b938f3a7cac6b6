"""setup_s: host wall seconds of the loop's set-up, its warm call included."""


def read(ctx):
    return ctx["setup"]["setup_s"]
