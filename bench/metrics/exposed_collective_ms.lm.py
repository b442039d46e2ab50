"""Milliseconds a round in which a collective runs on a chip while no other
operation does (the exchange not hidden behind compute), per chip."""


def read(ctx):
    t = ctx["trace"]
    if not t["collective_s"] or not t["rounds"]:
        return None
    return 1e3 * t["exposed_collective_s"] / t["rounds"]
