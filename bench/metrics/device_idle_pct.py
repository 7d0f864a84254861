"""Device: the share of the traced window in which no operation ran on the
chip (1 minus the union of the device's operation intervals)."""


def read(ctx):
    busy = ctx.view.busy_s()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx.view.window_s)
