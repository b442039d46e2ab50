"""Device milliseconds a round spends under the `dfedpgp.mix` scope (the
push-sum mix of the shared buffer and the weights), per chip.  The compiler
fuses the last u-step's SGD update of the shared buffer into the mix and
names the fusion after the mix, so this time holds that update too."""


def read(ctx):
    t = ctx["trace"]
    s = t["scope_s"].get("dfedpgp.mix")
    if not s or not t["rounds"]:
        return None
    return 1e3 * s / t["rounds"]
