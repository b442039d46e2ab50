"""Record the CPU trace fixture of `test_bench_spans.py` (logic only: CPU
times say nothing about a chip).

    JAX_PLATFORMS=cpu python -m bench.tests.record_span_fixture

A small jitted "round" whose `dfedpgp.local` scope holds a nested
`dfedpgp.u_update` scope, then a `dfedpgp.mix` scope, driven three times
inside the harness's `bench.window` / `bench.round` host spans as
`bench/drivers/lm_train.py` drives the program: an eager draw in
`bench.batches`, the program's own `TopologySchedule.at` (its
`dfedpgp.host.topology` span) in `bench.schedule`, the call in
`bench.dispatch` and the fetch in `bench.fetch`.  Writes
`fixtures/cpu_round_spans.xplane.pb` and the round's compiled text,
`fixtures/cpu_round_spans.hlo.txt`.
"""
import glob
import os
import shutil
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.core.topology import TopologySchedule

HERE = Path(__file__).resolve().parent / "fixtures"
NAME = "cpu_round_spans"
ROUNDS = 3
M = 4


@jax.jit
def round_jit(x, idx):
    with jax.named_scope("dfedpgp.local"):
        g = jnp.tanh(x @ x.T) @ x
        with jax.named_scope("dfedpgp.u_update"):
            x = x - 0.1 * (g @ x.T) @ x
    with jax.named_scope("dfedpgp.mix"):
        rows = x.reshape(M, -1, x.shape[1])       # one block a client
        x = (0.5 * (rows + rows[idx[:, 1]])).reshape(x.shape)
    return x


def main():
    schedule = TopologySchedule.exponential(M)
    x = jnp.ones((256, 256)) / 256
    for r in range(ROUNDS):                    # compile every call first
        jax.device_get(round_jit(x, schedule.at(r).idx)[0, 0])
    jax.random.normal(jax.random.PRNGKey(0), (64,)).block_until_ready()
    span = jax.profiler.TraceAnnotation
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with span("bench.window"):
            for r in range(ROUNDS):
                with span("bench.round"):
                    with span("bench.batches"):
                        jax.random.normal(jax.random.PRNGKey(r), (64,))
                    with span("bench.schedule"):
                        P = schedule.at(r)
                    with span("bench.dispatch"):
                        x = round_jit(x, P.idx)
                    with span("bench.fetch"):
                        jax.device_get(x[0, 0])
            x.block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                         recursive=True)[0]
        HERE.mkdir(exist_ok=True)
        shutil.copy(path, HERE / f"{NAME}.xplane.pb")
    text = round_jit.lower(x, P.idx).compile().as_text()
    # source paths relative to the checkout, wherever it was recorded
    root = str(Path(__file__).resolve().parents[2]) + os.sep
    (HERE / f"{NAME}.hlo.txt").write_text(text.replace(root, ""))


if __name__ == "__main__":
    main()
