"""The nested scopes and the idle partition (`bench/spans.py`), by hand and on
a small trace recorded on the CPU (`fixtures/cpu_round_spans.xplane.pb`,
made by `record_span_fixture.py`).  The fixtures check the logic only:
their times are the CPU's and mean nothing for a chip.
"""
import shutil
from pathlib import Path

import pytest

from bench import reduce, run, spans

FIX = Path(__file__).resolve().parent / "fixtures"
OLD = "cpu_round"             # recorded before the program had the spans
NEW = "cpu_round_spans"
# what `reduce.reduce` gives; its keys and values stay as they are
REDUCE_KEYS = {"window_s", "busy_s", "rounds", "devices", "scope_s",
               "collective_s", "exposed_collective_s", "breakdown"}


def _reduce(name):
    return reduce.reduce(str(FIX / f"{name}.xplane.pb"),
                         (FIX / f"{name}.hlo.txt").read_text(), [0])


@pytest.fixture(scope="module")
def new():
    return _reduce(NEW), spans.reduce_spans(str(FIX / f"{NEW}.xplane.pb"))


# ---------------------------------------------------------------------------
# the partition, by hand
# ---------------------------------------------------------------------------
def test_idle_partition_by_hand():
    # two rounds [0, 40) and [50, 90), each holding a schedule span with a
    # topology draw inside it; the window is [0, 100)
    sp = [(0, 40, "bench.round"), (10, 30, "bench.schedule"),
          (12, 28, "dfedpgp.host.topology"),
          (50, 90, "bench.round"), (60, 80, "bench.schedule"),
          (62, 78, "dfedpgp.host.topology")]
    busy = [(0, 5), (35, 38), (86, 95)]
    idle = reduce.gaps(busy, 0, 100)
    assert idle == [(5, 35), (38, 86), (95, 100)]
    got = spans.idle_by_span(idle, sp)
    assert got == {
        # [5, 35) crosses the schedule's and the draw's edges
        "bench.round": (10 - 5) + (35 - 30) + (40 - 38) + (60 - 50)
        + (86 - 80),
        "bench.schedule": (12 - 10) + (30 - 28) + (62 - 60) + (80 - 78),
        "dfedpgp.host.topology": (28 - 12) + (78 - 62),
        # [38, 86) crosses the gap between the two rounds
        spans.OUTSIDE: (50 - 40) + (100 - 95),
    }
    assert sum(got.values()) == 100 - reduce.length(busy)


def test_idle_partition_edge_cases():
    # no spans: everything is outside; an idle piece ending on an edge
    assert spans.idle_by_span([(0, 10)], []) == {spans.OUTSIDE: 10}
    assert spans.idle_by_span([(0, 10)], [(0, 10, "bench.round")]) == \
        {"bench.round": 10}
    # two spans over the same interval: the program's own is the inner one
    same = [(0, 10, "bench.schedule"), (0, 10, "dfedpgp.host.topology")]
    assert spans.idle_by_span([(2, 4)], same) == \
        {"dfedpgp.host.topology": 2}
    # spans that overlap without nesting: the shorter one wins
    cross = [(0, 10, "bench.a"), (5, 8, "bench.b")]
    assert spans.idle_by_span([(4, 9)], cross) == \
        {"bench.a": 2, "bench.b": 3}


# ---------------------------------------------------------------------------
# the HLO the profiler stores with a trace
# ---------------------------------------------------------------------------
def test_op_names_from_the_trace_match_the_compiled_text():
    raw = (FIX / f"{NEW}.xplane.pb").read_bytes()
    progs = spans.program_op_names(raw)
    module, table = reduce.op_names((FIX / f"{NEW}.hlo.txt").read_text())
    ours = [t for (_, mod), t in progs.items() if mod == module]
    assert len(ours) == 1
    assert {k: ours[0][k] for k in table} == table
    assert any("dfedpgp.u_update" in v for v in table.values())


def test_program_ids_of_module_names():
    assert spans._program_id("jit_round_fn(3469363036114191666)") == \
        3469363036114191666
    assert spans._program_id("jit_round_fn") is None
    assert spans._program_id("(12)") is None


# ---------------------------------------------------------------------------
# the fixtures
# ---------------------------------------------------------------------------
def test_reduce_keys_are_unchanged_on_the_old_fixture():
    red = _reduce(OLD)
    assert set(red) == REDUCE_KEYS
    assert set(red["breakdown"]) == {"device_ops", "idle_gaps"}
    ours = spans.reduce_spans(str(FIX / f"{OLD}.xplane.pb"))
    # the same window and the same busy time, read the same way
    assert ours["window_s"] == red["window_s"]
    assert ours["busy_s"] == pytest.approx(red["busy_s"], abs=1e-12)
    assert ours["rounds"] == red["rounds"] == 3
    # a program without the nested scope or the program's spans
    assert ours["subscope_s"] == {}
    assert not any(k.startswith(spans.PROGRAM_SPAN)
                   for k in ours["idle_by_span"])


@pytest.mark.parametrize("name", [OLD, NEW])
def test_idle_by_span_sums_to_the_idle_time(name):
    out = spans.reduce_spans(str(FIX / f"{name}.xplane.pb"))
    assert sum(out["idle_by_span"].values()) == \
        pytest.approx(out["window_s"] - out["busy_s"], rel=1e-9)
    assert all(v > 0 for v in out["idle_by_span"].values())


def test_nested_scope_and_topology_span_on_the_new_fixture(new):
    red, ours = new
    assert set(red) == REDUCE_KEYS
    assert ours["window_s"] == red["window_s"]
    assert ours["rounds"] == red["rounds"] == 3
    u = ours["subscope_s"]["dfedpgp.u_update"]
    # nested in the local scope, which `reduce` still names it by
    assert 0 < u < red["scope_s"]["dfedpgp.local"]
    assert "dfedpgp.u_update" not in red["scope_s"]
    idle = ours["idle_by_span"]
    assert idle["dfedpgp.host.topology"] > 0
    # the old naming gives every gap to the `bench.*` span most over it
    old_names = {n for n, _ in red["breakdown"]["idle_gaps"]}
    assert not any(n.startswith(spans.PROGRAM_SPAN) for n in old_names)


def _read(name, ctx):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           "r_" + name.replace(".", "_")).read(ctx)


@pytest.fixture
def traces(tmp_path, monkeypatch):
    """A `.bench_out/trace` of its own, as `bench/run.py` leaves it."""
    monkeypatch.setattr(spans, "TRACES", tmp_path)

    def leave(name):
        d = tmp_path / "cell" / "plugins" / "profile" / name
        d.mkdir(parents=True)
        shutil.copy(FIX / f"{name}.xplane.pb", d / "host.xplane.pb")
        return _reduce(name)
    return leave


def test_readers_on_the_new_fixture(traces, new):
    red = traces(NEW)
    _, ours = new
    ctx = {"trace": red, "chips": 1}
    assert _read("u_update_ms.lm", ctx) == pytest.approx(
        1e3 * ours["subscope_s"]["dfedpgp.u_update"] / 3)
    assert _read("topology_idle_ms.lm", ctx) == pytest.approx(
        1e3 * ours["idle_by_span"]["dfedpgp.host.topology"] / 3)
    # another window than the one the context was reduced from
    other = dict(red, window_s=red["window_s"] + 1e-3)
    assert _read("u_update_ms.lm", {"trace": other, "chips": 1}) is None


def test_readers_are_silent_for_a_program_without_the_spans(traces):
    ctx = {"trace": traces(OLD), "chips": 1}
    assert _read("u_update_ms.lm", ctx) is None
    assert _read("topology_idle_ms.lm", ctx) is None


def test_readers_are_silent_without_a_trace(traces):
    ctx = {"trace": _reduce(NEW), "chips": 1}
    assert _read("u_update_ms.lm", ctx) is None
    assert _read("topology_idle_ms.lm", ctx) is None


def test_readers_are_silent_on_a_trace_they_cannot_read(traces, tmp_path):
    ctx = {"trace": traces(NEW), "chips": 1}
    bad = tmp_path / "cell" / "plugins" / "profile" / "z" / "x.xplane.pb"
    bad.parent.mkdir(parents=True)
    bad.write_bytes(b"\x0a\xff\xff")           # a truncated message
    assert _read("u_update_ms.lm", ctx) is None
    assert _read("topology_idle_ms.lm", ctx) is None
