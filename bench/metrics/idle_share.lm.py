"""Share of the traced window in which no operation ran on the device (the
host loop between rounds: batch draw, schedule, dispatch, metrics fetch)."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
