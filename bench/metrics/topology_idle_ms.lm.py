"""Milliseconds a round in which device 0 is idle and the innermost host
span is the program's topology draw (`dfedpgp.host.topology`, around
`TopologySchedule.at`).  Nothing where the program has no such span."""


def read(ctx):
    from bench import spans
    t = spans.of_run(ctx)
    if t is None or not t["rounds"]:
        return None
    s = t["idle_by_span"].get("dfedpgp.host.topology")
    return 1e3 * s / t["rounds"] if s else None
