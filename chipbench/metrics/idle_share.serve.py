"""Device: the share of the traced window in which no operation ran on
the device (1 - union of device-op intervals over the window), averaged
over the chips, in %."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None:
        return None
    return 100.0 * tr.idle_share
