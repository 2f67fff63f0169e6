"""Share of the traced window in which the chips ran no operation
(mean over the cell's chips), in percent."""


def read(ctx):
    t = ctx["trace"]
    if not t.chips or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
