"""Share of the traced window in which no op runs on the device (1 - busy
over window, averaged over the cell's chips)."""


def read(ctx):
    tr = ctx["trace"]
    if not tr.ops or tr.window_s <= 0:
        return None
    return 1.0 - tr.busy_s() / tr.window_s
