"""The four-chip cell's own fault: the exchange between chips left out.

The `lm_train` driver on a mesh, one client per chip: four clients on an
exponential one-peer graph, mixed by ppermute, judged by the cell's
limits.  Runs in a child process with four virtual CPU devices (the
device count is fixed when JAX starts), at the small LM sizes of
`tiny.py`: a sound run is correct, and one whose ppermute mix returns
each chip's row unchanged is not."""
import json
import os
import subprocess
import sys

import pytest

from bench.run import ROOT

CHILD = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import jax
from bench import run
from bench.tests import tiny

s = tiny.spec("qwen2-0.5b.ici4")
if sys.argv[2] == "exchange_left_out":
    from repro.launch import steps
    made = steps.make_ppermute_mix_flat

    def no_exchange(*a, **k):
        made(*a, **k)
        return lambda flat, mu, rnd, P=None: (flat, mu)

    steps.make_ppermute_mix_flat = no_exchange
out = run.run_cell(s, 2 ** 31 + 77, 0.3, False, jax.devices()[:4])
print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
"""


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange_left_out", False)])
def test_the_exchange_between_chips(fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is correct, out["checks"]
