"""Record the CPU trace fixture of `test_bench_reduce.py` (logic only: CPU
times say nothing about a chip).

    JAX_PLATFORMS=cpu python -m bench.tests.record_fixture

A small jitted "round" with a `dfedpgp.local` and a `dfedpgp.mix` scope,
driven three times inside the harness's `bench.window` / `bench.round`
host spans, with an eager draw in a `bench.batches` span before each.
Writes `fixtures/cpu_round.xplane.pb` and the round's compiled text,
`fixtures/cpu_round.hlo.txt`.
"""
import glob
import os
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parent / "fixtures"
ROUNDS = 3


@jax.jit
def round_jit(x):
    with jax.named_scope("dfedpgp.local"):
        y = jnp.tanh(x @ x.T) @ x
    with jax.named_scope("dfedpgp.mix"):
        y = 0.5 * (y + jnp.roll(y, 1, axis=0))
    return y


def main():
    x = jnp.ones((256, 256))
    round_jit(x).block_until_ready()
    jax.random.normal(jax.random.PRNGKey(0), (64,)).block_until_ready()
    span = jax.profiler.TraceAnnotation
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with span("bench.window"):
            for r in range(ROUNDS):
                with span("bench.round"):
                    with span("bench.batches"):
                        jax.random.normal(jax.random.PRNGKey(r), (64,))
                    with span("bench.dispatch"):
                        x = round_jit(x)
                x.block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        HERE.mkdir(exist_ok=True)
        shutil.copy(path, HERE / "cpu_round.xplane.pb")
    text = round_jit.lower(x).compile().as_text()
    (HERE / "cpu_round.hlo.txt").write_text(text)


if __name__ == "__main__":
    main()
