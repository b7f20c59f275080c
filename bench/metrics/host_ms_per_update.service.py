"""Host ms per update: the traced window less the chip's busy time, over
the updates processed in it."""


def read(ctx):
    t = ctx["trace"]
    if not ctx["updates"]:
        return None
    return 1e3 * (t.window_s - t.busy_s) / ctx["updates"]
