"""The check that decides `correct` catches what it must, on the CPU at
small sizes: a whole run with the timed path broken underneath reads not
correct, and so does the control (the reference at fp8 products put in the
program's place).  The look for a chip is skipped; the rest of the run is
the harness's own.  Each `test_bench_correct_<cell>.py` runs these cases
for one cell, given by its `cell` fixture."""
import jax
import pytest

from bench import correct, run
from bench.tests import tiny


def _run(cell):
    return run.run_cell(tiny.one_device(tiny.spec(cell)), 2 ** 31 + 77, 0.3,
                        False, jax.devices()[:1])


def test_a_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in run.load_cell(cell)["end_to_end"]}
    assert set(out["metrics"]) == e2e
    # the CPU has no memory reading; the set-up and the rate are never 0
    assert all(out["metrics"][n]["value"] > 0 for n in e2e
               if n != "peak_hbm_gib")


def _unchanged(orig):
    def round_fn_flat(self, state, P, batches, layout, step_gate_u=None):
        _, metrics = orig(self, state, P, batches, layout, step_gate_u)
        return state, metrics
    return round_fn_flat


def _half_batch(orig):
    def round_fn_flat(self, state, P, batches, layout, step_gate_u=None):
        half = jax.tree.map(lambda a: a[:, :, :a.shape[2] // 2], batches)
        return orig(self, state, P, half, layout, step_gate_u)
    return round_fn_flat


FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch}


@pytest.mark.parametrize("fault", sorted(FAULTS) + ["mix_left_out"])
def test_a_broken_round_is_not_correct(cell, fault, monkeypatch):
    from repro.core import dfedpgp, gossip
    if fault == "mix_left_out":
        monkeypatch.setattr(gossip, "mix_flat",
                            lambda P, flat, mu, **kw: (flat, mu))
    else:
        monkeypatch.setattr(dfedpgp.DFedPGP, "round_fn_flat",
                            FAULTS[fault](dfedpgp.DFedPGP.round_fn_flat))
    out = _run(cell)
    assert not out["correct"], out["checks"]


def test_the_control_is_not_correct(cell):
    s = tiny.one_device(tiny.spec(cell))
    mod = run.load_module(s["driver"], "bench_driver_control")
    drv = mod.Driver(s["config"], s["traffic"], 5, jax.devices()[:1])
    ref = drv.reference()
    control = drv.reference(precision="fp8")
    ok, checks = correct.judge(correct.gaps(control, ref), tiny.limits(cell))
    assert not ok, checks
