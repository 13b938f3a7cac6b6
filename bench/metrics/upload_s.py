"""upload_s: host wall seconds of ``stage_upload`` until the arrays are on their devices."""


def read(ctx):
    return ctx["setup"]["upload_s"]
