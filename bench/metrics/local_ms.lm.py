"""Device milliseconds a round spends under the `dfedpgp.local` scope (the
u- and v-steps of every client), per chip."""


def read(ctx):
    t = ctx["trace"]
    s = t["scope_s"].get("dfedpgp.local")
    if not s or not t["rounds"]:
        return None
    return 1e3 * s / t["rounds"]
