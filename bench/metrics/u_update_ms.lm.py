"""Device milliseconds a round under the `dfedpgp.u_update` scope (the
u-step update: SGD on the shared row and the step-gate blend, nested in
`dfedpgp.local`), per chip.  Nothing where the program has no such scope."""


def read(ctx):
    from bench import spans
    t = spans.of_run(ctx)
    if t is None or not t["rounds"]:
        return None
    s = t["subscope_s"].get("dfedpgp.u_update")
    return 1e3 * s / t["rounds"] if s else None
