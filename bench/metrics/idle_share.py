"""idle_share: 1 - union of device-op intervals / traced window, on the busiest device, in %."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr.get("idle_share") is None:
        return None
    return 100.0 * tr["idle_share"]
