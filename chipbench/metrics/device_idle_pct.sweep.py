"""Device idle share of the traced stretch of the window, in percent: one
minus the union of op intervals over the traced window, averaged over the
cell's devices."""


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.ops or tr.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
