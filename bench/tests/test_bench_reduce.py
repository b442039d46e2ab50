"""The trace reduction, on a small trace recorded on the CPU
(`fixtures/cpu_round.xplane.pb`, made by `record_fixture.py`).  The fixture
checks the logic only: its times are the CPU's and mean nothing for a chip.
"""
from pathlib import Path

import pytest

from bench import reduce

FIX = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture(scope="module")
def reduced():
    return reduce.reduce(str(FIX / "cpu_round.xplane.pb"),
                         (FIX / "cpu_round.hlo.txt").read_text(), [0])


def test_interval_arithmetic_by_hand():
    u = reduce.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [[0, 3], [5, 9]]
    assert reduce.length(u) == 7
    assert reduce.subtract([(0, 10)], u) == [(3, 5), (9, 10)]
    assert reduce.gaps(u, 0, 12) == [(3, 5), (9, 12)]
    assert reduce.subtract([[0, 3], [5, 9]], [[2, 6]]) == [(0, 2), (6, 9)]


def test_op_names_of_the_compiled_round():
    module, table = reduce.op_names((FIX / "cpu_round.hlo.txt").read_text())
    assert module.startswith("jit_round_jit")
    scopes = {reduce.scope_of(v) for v in table.values()}
    assert {"dfedpgp.local", "dfedpgp.mix"} <= scopes


def test_window_rounds_and_busy(reduced):
    assert reduced["rounds"] == 3
    assert reduced["devices"] == 1
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_busy_is_the_union_of_the_operations_in_the_window(reduced):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIX / "cpu_round.xplane.pb"))
    host, ops = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if ev.name == "bench.window":
                    host.append(iv)
                elif "hlo_op" in dict(ev.stats):
                    ops.append(iv)
    (lo, hi), = host
    busy = reduce.union([(max(a, lo), min(b, hi)) for a, b in ops
                         if b > lo and a < hi])
    assert reduced["busy_s"] == pytest.approx(reduce.length(busy) * 1e-9)
    assert reduced["window_s"] == pytest.approx((hi - lo) * 1e-9)
    idle = sum(s for _, s in reduced["breakdown"]["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"])


def test_scopes_and_breakdown(reduced):
    s = reduced["scope_s"]
    assert s["dfedpgp.local"] > 0 and s["dfedpgp.mix"] > 0
    assert s["dfedpgp.local"] + s["dfedpgp.mix"] <= reduced["busy_s"] + 1e-12
    ops = reduced["breakdown"]["device_ops"]
    assert 0 < len(ops) <= reduce.TOP
    assert any(name.startswith("dfedpgp.local:") for name, _ in ops)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    assert set(gaps) <= {"bench.round", "bench.batches", "bench.dispatch",
                         "host:outside bench spans"}
    assert reduced["collective_s"] == 0.0


def test_readers_on_the_fixture(reduced):
    from bench import run
    ctx = {"trace": reduced, "chips": 1, "round_ops": 1e9,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    read = lambda n: run.load_module(run.BENCH / "metrics" / f"{n}.py",
                                     "r_" + n.replace(".", "_")).read(ctx)
    w, b = reduced["window_s"], reduced["busy_s"]
    assert read("idle_share.lm") == pytest.approx(100 * (1 - b / w))
    per_round = reduced["scope_s"]["dfedpgp.local"] / 3
    assert read("local_ms.lm") == pytest.approx(1e3 * per_round)
    assert read("mfu.lm") == pytest.approx(100 * 3e9 / (w * 197e12))
    mix = reduced["scope_s"]["dfedpgp.mix"] / 3
    assert read("mix_ms.lm") == pytest.approx(1e3 * mix)


def test_exposed_collective_time_is_its_self_time():
    # a loop [0, 100) holds a collective wait [10, 40), a computation
    # [50, 60) and a collective start [60, 70); the loop covering the
    # collectives does not hide them
    ev = [(0, 100, "while.1"), (10, 40, "collective-permute-done"),
          (50, 60, "fusion.2"), (60, 70, "collective-permute-start")]
    assert reduce.exposed_collective(ev) == 40
    # a computation nested in a collective's span is hidden time
    ev = [(0, 30, "all-reduce.3"), (5, 25, "fusion.4")]
    assert reduce.exposed_collective(ev) == 10


def test_self_time_of_nested_operations():
    # a loop [0, 10) holding two body ops [1, 3) and [4, 8), then [12, 13)
    ev = [(0, 10, "while"), (1, 3, "a"), (4, 8, "b"), (12, 13, "c")]
    assert reduce.self_times(ev) == [4, 2, 4, 1]
