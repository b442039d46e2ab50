"""The telemetry spine (repro.obs; docs/observability.md).

The load-bearing claim is the OFF contract: telemetry gauges ride the
round as extra aux on the same donated buffer, gated by a STATIC flag,
so the uninstrumented program is bit-for-bit the pre-obs program —
params, mu, BOTH momenta, mailbox.  Pinned here for the resident sync
round (Regime A), the sampled round, the launch-layer builder path
(Regime B wiring), and the async tick.

Also under test: the record schema round-trip, sinks, the gauge
definitions themselves (mass ledger conservation, consensus gap
monotone under averaging), the report CLI's mass gate, and the
check_regression schema pin.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import dfedpgp, sampling, topology
from repro.hetero import profiles
from repro.hetero.runtime import AsyncRuntime
from repro.obs import gauges, record, report
from repro.optim import SGD
from repro.serve import ServeMeter

ROOT = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# fixtures (the repo's closed-form DFedPGP harness)
# ---------------------------------------------------------------------------
def _quad(m=8, d=6, dp=3):
    key = jax.random.PRNGKey(0)
    cu = jax.random.normal(key, (m, d))
    cv = jax.random.normal(jax.random.fold_in(key, 1), (m, dp))

    def loss_fn(p, b):
        return jnp.sum((p["body"] - b["tu"][0]) ** 2) + \
            jnp.sum((p["head"] - b["tv"][0]) ** 2)

    return loss_fn, {"body": True, "head": False}, cu, cv


def _batches(cu, cv, k):
    rep = lambda x: jnp.repeat(x[:, None], k, 1)[..., None, :]
    return {"v": {"tu": rep(cu), "tv": rep(cv)},
            "u": {"tu": rep(cu), "tv": rep(cv)}}


def _algo(loss_fn, mask, **kw):
    opt = SGD(lr=0.1, momentum=0.9, weight_decay=5e-4)
    return dfedpgp.DFedPGP(loss_fn=loss_fn, mask=mask, opt_u=opt, opt_v=opt,
                           k_v=1, k_u=2, lr_decay=0.99, **kw)


def _assert_states_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.flat), np.asarray(b.flat))
    np.testing.assert_array_equal(np.asarray(a.mu), np.asarray(b.mu))
    np.testing.assert_array_equal(np.asarray(a.opt_u.momentum),
                                  np.asarray(b.opt_u.momentum))
    np.testing.assert_array_equal(np.asarray(a.personal["head"]),
                                  np.asarray(b.personal["head"]))
    np.testing.assert_array_equal(np.asarray(a.opt_v.momentum["head"]),
                                  np.asarray(b.opt_v.momentum["head"]))


GAUGE_KEYS = ("consensus_gap_mean", "consensus_gap_max", "mass_total",
              "update_norm", "grad_norm", "wire_edges")


# ---------------------------------------------------------------------------
# ACCEPTANCE: telemetry OFF is bit-for-bit the uninstrumented program
# ---------------------------------------------------------------------------
def test_telemetry_off_is_bitwise_identity_resident_round():
    """Resident Regime A: 3 rounds with telemetry=True vs telemetry=False
    leave IDENTICAL state — the gauges are read-only aux, and the static
    gate keeps them out of the off-path trace entirely."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    a_off = _algo(loss_fn, mask)
    a_on = _algo(loss_fn, mask, telemetry=True)
    params = {"body": cu, "head": cv}
    s_off, layout = a_off.init_flat(params)
    s_on, _ = a_on.init_flat(params)
    sched = topology.TopologySchedule.random(m, 3, seed=13)
    b = _batches(cu, cv, 2)
    for t in range(3):
        # column-stochastic push drifts mu != 1: gauge the hard regime
        P = topology.to_column_stochastic(sched.at(t))
        s_off, mt_off = jax.jit(
            lambda s, p, bb: a_off.round_fn_flat(s, p, bb, layout))(
                s_off, P, b)
        s_on, mt_on = jax.jit(
            lambda s, p, bb: a_on.round_fn_flat(s, p, bb, layout))(
                s_on, P, b)
        for k in GAUGE_KEYS:
            assert k in mt_on and k not in mt_off, k
        # shared metrics agree bit-for-bit too
        for k in mt_off:
            np.testing.assert_array_equal(np.asarray(mt_off[k]),
                                          np.asarray(mt_on[k]), err_msg=k)
    assert np.abs(np.asarray(s_on.mu) - 1.0).max() > 1e-3  # mu moved
    _assert_states_equal(s_on, s_off)


def test_telemetry_off_is_bitwise_identity_sampled_round():
    """The sampled (gather/round/scatter) path under 50% participation:
    same bit-for-bit OFF contract, and the mass ledger gauge accounts
    dormant rows separately."""
    loss_fn, mask, cu, cv = _quad()
    m = cu.shape[0]
    a_off = _algo(loss_fn, mask)
    a_on = _algo(loss_fn, mask, telemetry=True)
    params = {"body": cu, "head": cv}
    s_off, layout = a_off.init_flat(params)
    s_on, _ = a_on.init_flat(params)
    sched = topology.TopologySchedule.random(m, 3, seed=13)
    sampler = sampling.ParticipationSampler("uniform", m=m, frac=0.5,
                                            seed=5)
    b = _batches(cu, cv, 2)
    for t in range(3):
        active = jnp.asarray(sampler.active_at(t))
        P_act = topology.induced_subgraph(sched.at(t), active, "row")
        ba = {p: {k: v[active] for k, v in bb.items()}
              for p, bb in b.items()}
        s_off, _ = a_off.round_fn_sampled(s_off, P_act, active, ba, layout)
        s_on, mt_on = a_on.round_fn_sampled(s_on, P_act, active, ba, layout)
    _assert_states_equal(s_on, s_off)
    n_act = int(active.shape[0])
    np.testing.assert_allclose(float(mt_on["mass_active"])
                               + float(mt_on["mass_dormant"]),
                               float(mt_on["mass_total"]), rtol=1e-6)
    assert float(mt_on["mass_dormant"]) > 0  # 50%: dormant rows exist
    assert int(mt_on["n_active"]) == n_act


def test_telemetry_off_is_bitwise_identity_async_tick():
    """AsyncRuntime: the tick's telemetry block (consensus gap over the
    in-flight-aware ledger, mailbox occupancy, staleness) is metrics-only
    — buffer, mu, momenta and mailbox bit-identical over 6 ticks."""
    loss_fn, mask, cu, cv = _quad(m=6)
    m = cu.shape[0]
    a_off = _algo(loss_fn, mask)
    a_on = dataclasses.replace(a_off, telemetry=True)
    prof = profiles.tiered(m, spread=3.0, push_delay_max=2,
                           availability=0.8, seed=1)
    params = {"body": cu, "head": cv}
    rt_off, s_off = AsyncRuntime.build(a_off, params, prof, depth=3)
    rt_on, s_on = AsyncRuntime.build(a_on, params, prof, depth=3)
    b = _batches(cu, cv, 2)
    bt = {k: v[:, 0] for k, v in b["u"].items()}
    for t in range(6):
        topo = topology.to_push_sparse(
            topology.directed_random(jax.random.PRNGKey(300 + t), m, 2))
        s_off, mt_off = jax.jit(
            lambda s, p, b, rt=rt_off: rt.tick(s, p, b))(s_off, topo, bt)
        s_on, mt_on = jax.jit(
            lambda s, p, b, rt=rt_on: rt.tick(s, p, b))(s_on, topo, bt)
        assert "consensus_gap_mean" in mt_on
        assert "mailbox_slot_occupancy" in mt_on
        assert "staleness_max" in mt_on
        assert "consensus_gap_mean" not in mt_off
        # in-flight-aware total mass conserved at m (push-sum ledger)
        np.testing.assert_allclose(float(mt_on["mass_total"]), m,
                                   rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(s_off.flat),
                                  np.asarray(s_on.flat))
    np.testing.assert_array_equal(np.asarray(s_off.mu),
                                  np.asarray(s_on.mu))
    np.testing.assert_array_equal(np.asarray(s_off.mail.slots_flat),
                                  np.asarray(s_on.mail.slots_flat))
    np.testing.assert_array_equal(np.asarray(s_off.mail.inbox_mu),
                                  np.asarray(s_on.mail.inbox_mu))


def test_telemetry_off_is_bitwise_identity_regime_b():
    """Regime B wiring: build_train_algo consumes AlgoSpec.telemetry and
    the resulting LM round is bit-for-bit identical with the knob off —
    the CLI smoke's contract, pinned at test scale."""
    from repro.configs import get_reduced
    from repro.launch import steps
    from repro.models import get_model
    from repro.spec import make_algo_spec

    cfg = get_reduced("qwen2-0.5b")
    m, batch, seq, rounds = 2, 1, 16, 2
    layout = steps.Layout(("data",), (), ("model",), (), m, batch)

    def mk(telemetry):
        spec = make_algo_spec("dfedpgp", topology="ring", gossip="sparse",
                              resident=True, telemetry=telemetry)
        algo, mask, _, flat_layout = steps.build_train_algo(
            cfg, None, layout, k_u=1, k_v=1, spec=spec, lr=0.02)
        return algo, flat_layout, spec

    api = get_model(cfg)
    stacked = jax.vmap(lambda k: api.init_params(k, cfg))(
        jax.random.split(jax.random.PRNGKey(0), m))

    def synth(key, lead):
        toks = jax.random.randint(key, lead + (seq,), 0, cfg.vocab,
                                  jnp.int32)
        return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=-1)}

    states, metrics = [], []
    for telemetry in (False, True):
        algo, flat_layout, spec = mk(telemetry)
        assert algo.telemetry is telemetry
        state, flat_layout = algo.init_flat(stacked, flat_layout)
        sched = spec.schedule(m)
        for r in range(rounds):
            kb = jax.random.fold_in(jax.random.PRNGKey(9), r)
            batches = {"v": synth(kb, (m, 1, batch)),
                       "u": synth(jax.random.fold_in(kb, 7), (m, 1, batch))}
            state, mt = jax.jit(
                lambda s, p, bb, fl=flat_layout, a=algo:
                    a.round_fn_flat(s, p, bb, fl))(state, sched.at(r),
                                                   batches)
        states.append(state)
        metrics.append(mt)
    assert "consensus_gap_mean" in metrics[1]
    assert "consensus_gap_mean" not in metrics[0]
    np.testing.assert_array_equal(np.asarray(states[0].flat),
                                  np.asarray(states[1].flat))
    np.testing.assert_array_equal(np.asarray(states[0].mu),
                                  np.asarray(states[1].mu))
    np.testing.assert_array_equal(np.asarray(states[0].opt_u.momentum),
                                  np.asarray(states[1].opt_u.momentum))


def test_spec_rejects_telemetry_without_resident():
    from repro.spec import make_algo_spec
    with pytest.raises(ValueError, match="telemetry"):
        make_algo_spec("dfedpgp", resident=False, telemetry=True)


def test_round_fn_tree_rejects_telemetry():
    loss_fn, mask, cu, cv = _quad()
    algo = _algo(loss_fn, mask, telemetry=True)
    s = algo.init({"body": cu, "head": cv})
    P = topology.directed_random(jax.random.PRNGKey(0), cu.shape[0], 2)
    with pytest.raises(ValueError, match="telemetry"):
        algo.round_fn(s, P, _batches(cu, cv, 2))


# ---------------------------------------------------------------------------
# gauge definitions
# ---------------------------------------------------------------------------
def test_consensus_gap_monotone_under_full_graph_averaging():
    """Lazy full-graph averaging (P = I/2 + 11^T/2m) contracts every
    de-biased row toward the mass-weighted mean — the gap gauge must
    decrease strictly every mix and hit ~0 at consensus (the gauge's
    connection to the paper's Gamma(W); docs/observability.md)."""
    m, d = 8, 5
    flat = jax.random.normal(jax.random.PRNGKey(1), (m, d))
    mu = jnp.ones((m,))
    P = 0.5 * jnp.eye(m) + 0.5 * jnp.full((m, m), 1.0 / m)
    gaps = []
    for _ in range(6):
        g = gauges.consensus_gap(flat, mu)
        gaps.append(float(g["consensus_gap_mean"]))
        assert float(g["consensus_gap_max"]) >= gaps[-1] - 1e-7
        flat, mu = P @ flat, P @ mu
    assert all(b < a * 0.75 for a, b in zip(gaps, gaps[1:])), gaps
    assert gaps[-1] < 1e-1 * gaps[0]


def test_mass_ledger_partitions_total():
    m = 10
    mu = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (m,))) + 0.5
    mask = jnp.arange(m) < 4
    in_flight = jnp.asarray(0.7)
    g = gauges.mass_ledger(mu, mask, in_flight)
    np.testing.assert_allclose(
        float(g["mass_active"]) + float(g["mass_dormant"])
        + float(g["mass_in_flight"]), float(g["mass_total"]), rtol=1e-6)
    np.testing.assert_allclose(float(g["mass_active"]),
                               float(mu[:4].sum()), rtol=1e-6)
    np.testing.assert_allclose(float(g["mass_in_flight"]), 0.7, rtol=1e-6)
    # no mask: everything is active
    g_all = gauges.mass_ledger(mu)
    np.testing.assert_allclose(float(g_all["mass_active"]),
                               float(mu.sum()), rtol=1e-6)
    assert float(g_all["mass_dormant"]) == 0.0


def test_ef_signal_ratio_bounds_and_gamma_consistency():
    """The EF gauge IS the codec_gamma='auto' signal (one definition,
    two consumers): in (0, 1], 1.0 when the residual is empty, small
    when the residual dominates."""
    from repro import compress

    flat = jax.random.normal(jax.random.PRNGKey(3), (4, 7))
    np.testing.assert_allclose(
        float(gauges.ef_signal_ratio(flat, jnp.zeros_like(flat))), 1.0,
        rtol=1e-6)
    r = float(gauges.ef_signal_ratio(flat, 100.0 * flat))
    assert 0.0 < r < 0.02
    loss_fn, mask, cu, cv = _quad(m=4)
    algo = _algo(loss_fn, mask,
                 codec=compress.make_codec("topk", ratio=0.25),
                 codec_gamma="auto")
    want = jnp.clip(gauges.ef_signal_ratio(cu, 0.5 * cu), 0.05, 1.0)
    got = algo._gamma_value(cu, 0.5 * cu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_wire_edges_gauge_matches_host_edge_count():
    m = 12
    P = topology.directed_random(jax.random.PRNGKey(4), m, 3)
    assert int(gauges.wire_edges(P)) == gauges.edge_count(P)
    dense = P.dense()
    assert int(gauges.wire_edges(dense)) == gauges.edge_count(dense)
    # fired mask: only edges whose SOURCE fired count
    fired = jnp.arange(m) % 2 == 0
    assert int(gauges.wire_edges(P, fired)) <= int(gauges.wire_edges(P))


def test_payload_row_bytes_matches_codec_accounting():
    from repro import compress
    d = 64
    assert gauges.payload_row_bytes(None, d) == 4 * d + compress.MU_BYTES
    c = compress.make_codec("topk", ratio=0.25)
    assert gauges.payload_row_bytes(c, d) == c.row_bytes(d)
    assert gauges.bootstrap_bytes(None, 8, d) == 0
    assert gauges.bootstrap_bytes(c, 8, d) == 8 * 4 * d


# ---------------------------------------------------------------------------
# records, sinks, report
# ---------------------------------------------------------------------------
def test_record_roundtrip_jsonl(tmp_path):
    recs = [
        obs.round_record(run="r", algo="dfedpgp", step=1, loss=0.5,
                         wire_bytes=1024, mass_total=8.0),
        obs.tick_record(run="r", algo="dfedpgp", step=2, vtime=3.5,
                        wire_bytes=2048),
        obs.serve_record(run="s", step=1, path="fused", batch=64,
                         latency_ms=1.25),
    ]
    p = tmp_path / "run.jsonl"
    with obs.JsonlSink(str(p)) as sink:
        for r in recs:
            sink.emit(r)
    back = list(record.load_jsonl(str(p)))
    assert back == recs
    assert record.schema_of(back) == obs.SCHEMA_VERSION
    # 0-d jax arrays unwrap; non-finite floats map to None (JSON-safe)
    r = obs.round_record(step=0, wire_bytes=0, gap=jnp.float32(2.0),
                         bad=float("nan"))
    assert r["gap"] == 2.0
    assert r["bad"] is None
    record.validate(r)


def test_record_validation_rejects_malformed():
    with pytest.raises(ValueError, match="required"):
        record.validate(record.make_record("round", step=1))   # no wire_bytes
    with pytest.raises(ValueError, match="kind"):
        record.validate(record.make_record("vibes", step=1))
    newer = obs.round_record(step=1, wire_bytes=0)
    newer["schema"] = obs.SCHEMA_VERSION + 1
    with pytest.raises(ValueError, match="newer"):
        record.validate(newer)
    bad = obs.round_record(step=1, wire_bytes=0)
    bad["blob"] = [1, 2, 3]
    with pytest.raises(ValueError, match="JSON scalar"):
        record.validate(bad)
    # JsonlSink validates at the WRITE site
    sink = obs.JsonlSink("/dev/null")
    with pytest.raises(ValueError):
        sink.emit({"kind": "round"})
    sink.close()


def test_sinks_ring_tee_null():
    ring = obs.RingSink(capacity=3)
    for i in range(5):
        ring.emit(obs.round_record(step=i, wire_bytes=i))
    assert [r["step"] for r in ring.records] == [2, 3, 4]
    assert ring.last("round")["step"] == 4
    assert ring.last("serve") is None
    ring2 = obs.RingSink()
    tee = obs.TeeSink(ring2, obs.NULL_SINK)
    tee.emit(obs.serve_record(step=1, path="fused", batch=1,
                              latency_ms=0.5))
    assert ring2.last("serve")["batch"] == 1
    for s in (ring, ring2, tee, obs.NULL_SINK):
        assert isinstance(s, obs.MetricsSink)
        s.close()


def test_report_check_gates_mass_drift(tmp_path, capsys):
    ok, drift = tmp_path / "ok.jsonl", tmp_path / "drift.jsonl"
    with obs.JsonlSink(str(ok)) as s:
        for i in range(4):
            s.emit(obs.round_record(run="a", step=i, wire_bytes=100 * i,
                                    mass_total=8.0 + i * 1e-6))
    with obs.JsonlSink(str(drift)) as s:
        for i in range(4):
            s.emit(obs.round_record(run="a", step=i, wire_bytes=100 * i,
                                    mass_total=8.0 + i * 0.5))
    assert report.main([str(ok), "--check"]) == 0
    assert "report: OK" in capsys.readouterr().out
    assert report.main([str(drift), "--check"]) == 1
    assert "MASS LEDGER DRIFT" in capsys.readouterr().err
    # drift WITHIN a different run stream doesn't cross-contaminate
    both = tmp_path / "both.jsonl"
    with obs.JsonlSink(str(both)) as s:
        s.emit(obs.round_record(run="a", step=0, wire_bytes=0,
                                mass_total=8.0))
        s.emit(obs.round_record(run="b", step=0, wire_bytes=0,
                                mass_total=16.0))
    assert report.main([str(both), "--check"]) == 0
    capsys.readouterr()


def test_report_renders_simulator_runs(tmp_path, capsys):
    """ACCEPTANCE: sync + async simulator runs emit schema-valid JSONL
    the report CLI renders and --check passes (mass conserved)."""
    from repro.fl.simulator import SimConfig, run_experiment
    from repro.spec import make_algo_spec

    spec = make_algo_spec("dfedpgp", topology="random", n_neighbors=2,
                          resident=True, telemetry=True)
    sim = SimConfig(m=6, rounds=3, n_train=16, n_test=8, batch=8,
                    k_local=1, k_personal=1, spec=spec)
    p = tmp_path / "both.jsonl"
    with obs.JsonlSink(str(p)) as sink:
        run_experiment("dfedpgp", sim, eval_every=2, sink=sink)
        run_experiment("dfedpgp", dataclasses.replace(
            sim, runtime="async"), eval_every=2, sink=sink)
    recs = list(record.load_jsonl(str(p)))
    kinds = {r["kind"] for r in recs}
    assert kinds == {"round", "tick"}
    assert all("consensus_gap_mean" in r and "mass_total" in r
               for r in recs)
    assert all("t_round_s" in r for r in recs if r["kind"] == "round")
    assert report.main([str(p), "--check"]) == 0
    out = capsys.readouterr().out
    assert "round" in out and "tick" in out and "report: OK" in out


def test_serve_meter_records_and_stats():
    ring = obs.RingSink()
    meter = ServeMeter(sink=ring, window=8, run="t")
    for i in range(10):
        meter.observe("fused", 64, 0.001 * (i + 1))
    meter.observe("naive", 64, 0.5)
    st = {(r["path"], r["batch"]): r for r in meter.stats()}
    assert st[("fused", 64)]["calls"] == 10
    # window=8 keeps the LAST 8 calls: 3ms..10ms, nearest-rank p50 = 6ms
    assert st[("fused", 64)]["p50_ms"] == pytest.approx(6.0)
    assert st[("naive", 64)]["p50_ms"] == pytest.approx(500.0)
    recs = ring.records
    assert len(recs) == 11 and all(r["kind"] == "serve" for r in recs)
    for r in recs:
        record.validate(r)
    assert recs[0]["rps"] == pytest.approx(64 / 0.001)
    assert len(meter.latencies("fused", 64)) == 8
    meter.clear("fused", 64)
    assert meter.latencies("fused", 64) == []
    assert {(r["path"], r["batch"]) for r in meter.stats()} == \
        {("naive", 64)}


# ---------------------------------------------------------------------------
# cross-tool schema pins
# ---------------------------------------------------------------------------
def _load_check_regression():
    spec = importlib.util.spec_from_file_location(
        "check_regression", ROOT / "benchmarks" / "check_regression.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_regression_schema_pin(tmp_path):
    """benchmarks/check_regression.py runs without PYTHONPATH=src, so it
    carries a local pin of repro.obs.SCHEMA_VERSION — the two must move
    together, and a newer-stamped artifact must fail loudly."""
    cr = _load_check_regression()
    assert cr.SUPPORTED_SCHEMA == obs.SCHEMA_VERSION
    legacy = tmp_path / "legacy.json"
    legacy.write_text('{"bench": "serve", "rows": []}')
    assert cr.load(legacy) == {"bench": "serve", "rows": []}   # v0 ok
    newer = tmp_path / "newer.json"
    newer.write_text(
        '{"bench": "serve", "schema_version": %d, "rows": []}'
        % (obs.SCHEMA_VERSION + 1))
    with pytest.raises(SystemExit, match="newer"):
        cr.load(newer)


def test_committed_bench_serve_baseline_is_stamped():
    import json
    base = json.loads((ROOT / "BENCH_serve.json").read_text())
    assert base["schema_version"] == obs.SCHEMA_VERSION


def test_phase_timer_accumulates():
    t = obs.PhaseTimer()
    with t.phase("round"):
        pass
    with t.phase("round"):
        pass
    with t.phase("eval"):
        pass
    g = t.gauges()
    assert set(g) == {"t_round_s", "t_eval_s"}
    # gauges round to microseconds for the JSONL; seconds() is raw
    assert g["t_round_s"] >= 0
    assert t.seconds("round") == pytest.approx(g["t_round_s"], abs=1e-6)
    t.reset()
    assert t.gauges() == {}


def test_maybe_trace_falsy_is_noop(tmp_path):
    with obs.maybe_trace(None):
        x = jnp.ones(()) + 1
    assert float(x) == 2.0
    assert list(tmp_path.iterdir()) == []


def test_phase_timer_block_waits_on_the_yielded_result():
    """phase(block=True) yields a holder; whatever the body parks on
    .out is block_until_ready'd INSIDE the bucket, so the accumulated
    time covers device compute, not just dispatch."""
    t = obs.PhaseTimer()
    with t.phase("round", block=True) as ph:
        ph.out = jnp.ones((256, 256)) @ jnp.ones((256, 256))
    assert float(ph.out[0, 0]) == 256.0
    assert t.seconds("round") > 0
    # block=True with nothing parked is a plain timer (no crash)
    with t.phase("eval", block=True):
        pass
    assert set(t.gauges()) == {"t_round_s", "t_eval_s"}
    # default stays the old API: no holder needed, nothing blocked
    with t.phase("idle"):
        pass
    assert t.seconds("idle") >= 0


# ---------------------------------------------------------------------------
# schema versioning: committed v1 fixture + loud newer-schema rejection
# ---------------------------------------------------------------------------
V1_FIXTURE = ROOT / "tests" / "data" / "schema_v1.jsonl"


def test_schema_v1_fixture_loads_under_v2_readers(capsys):
    """Backwards compat is a committed artifact, not a comment: the
    schema-v1 JSONL written before the graph/alert kinds existed must
    keep loading, validating and reporting under the v2 readers."""
    recs = list(record.load_jsonl(str(V1_FIXTURE)))
    assert recs and record.schema_of(recs) == 1
    for r in recs:
        record.validate(r)                     # v2 reader, v1 records
    assert {r["kind"] for r in recs} == {"round", "tick", "serve"}
    assert report.main([str(V1_FIXTURE), "--check"]) == 0
    out = capsys.readouterr().out
    assert "schema v1" in out and "report: OK" in out


def test_newer_schema_jsonl_rejected_loudly(tmp_path, capsys):
    """A v3 stream (from some future writer) must fail the report gate
    with exit 1 — never a silent partial render."""
    import json
    p = tmp_path / "future.jsonl"
    rec = obs.round_record(run="f", algo="a", step=1, wire_bytes=0)
    rec["schema"] = obs.SCHEMA_VERSION + 1
    p.write_text(json.dumps(rec) + "\n")
    assert report.main([str(p), "--check"]) == 1
    assert "newer" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# serve-meter stats edge cases + report --diff
# ---------------------------------------------------------------------------
def test_serve_meter_stats_edge_cases():
    meter = ServeMeter(sink=obs.NullSink(), window=4, run="t")
    # single sample: p50 == p99 == the sample
    meter.observe("fused", 32, 0.002)
    st = {(r["path"], r["batch"]): r for r in meter.stats()}
    row = st[("fused", 32)]
    assert row["p50_ms"] == row["p99_ms"] == pytest.approx(2.0)
    assert row["rps"] == pytest.approx(32 / 0.002)
    # p50 == 0 (clock too coarse to resolve): rps is None, not a crash
    meter.observe("naive", 8, 0.0)
    st = {(r["path"], r["batch"]): r for r in meter.stats()}
    assert st[("naive", 8)]["rps"] is None
    # the live serve record: rps=None means the gauge is OMITTED (the
    # JSONL carries no key), never a bogus number
    ring = obs.RingSink()
    m2 = ServeMeter(sink=ring, window=4, run="t")
    m2.observe("naive", 8, 0.0)
    assert "rps" not in ring.records[-1]
    record.validate(ring.records[-1])
    # empty window (cleared tag) is skipped, not rendered as NaN
    meter.clear("fused", 32)
    assert ("fused", 32) not in {(r["path"], r["batch"])
                                 for r in meter.stats()}
    # identical samples: every percentile is that value
    for _ in range(4):
        meter.observe("tie", 16, 0.003)
    st = {(r["path"], r["batch"]): r for r in meter.stats()}
    assert st[("tie", 16)]["p50_ms"] == st[("tie", 16)]["p99_ms"] \
        == pytest.approx(3.0)


def test_report_percentile_matches_meter_definition():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert report.percentile(xs, 50) == 3.0     # nearest-rank, no interp
    assert report.percentile(xs, 0) == 1.0
    assert report.percentile(xs, 100) == 5.0
    assert report.percentile([7.0], 99) == 7.0
    assert np.isnan(report.percentile([], 50))
    meter = ServeMeter(sink=obs.NullSink(), window=8, run="t")
    for x in xs:
        meter.observe("p", 1, x * 1e-3)
    row = meter.stats()[0]
    assert row["p50_ms"] == pytest.approx(report.percentile(xs, 50))
    assert row["p99_ms"] == pytest.approx(report.percentile(xs, 99))


def _jsonl(path, recs):
    import json
    path.write_text("".join(json.dumps(r) + "\n" for r in recs))


def test_report_diff_step_aligned(tmp_path, capsys):
    a = tmp_path / "a.jsonl"
    b = tmp_path / "b.jsonl"
    _jsonl(a, [obs.round_record(run="a", algo="x", step=s, loss=1.0 / s,
                                mass_total=8.0, wire_bytes=100 * s)
               for s in (1, 2, 3)])
    # b misses step 3 (diverged run) and improves the loss at 1, 2
    _jsonl(b, [obs.round_record(run="b", algo="x", step=s, loss=0.5 / s,
                                mass_total=8.0, wire_bytes=100 * s)
               for s in (1, 2)])
    assert report.main([str(a), str(b), "--diff"]) == 0
    out = capsys.readouterr().out
    assert "diff:round" in out and "d_loss" in out
    # only the aligned steps appear
    lines = [ln for ln in out.splitlines() if ln.strip()
             and ln.split()[0].isdigit()]
    assert [ln.split()[0] for ln in lines] == ["1", "2"]
    # the delta column carries b - a = -0.5/s
    assert "-0.5" in lines[0]


def test_report_diff_exit_codes(tmp_path, capsys):
    a = tmp_path / "a.jsonl"
    _jsonl(a, [obs.round_record(run="a", algo="x", step=1, wire_bytes=0)])
    # wrong file count is usage error: exit 2
    assert report.main([str(a), "--diff"]) == 2
    # two files but zero step-aligned records: exit 1
    b = tmp_path / "b.jsonl"
    _jsonl(b, [obs.serve_record(run="b", step=1, path="fused", batch=1,
                                latency_ms=1.0)])
    assert report.main([str(a), str(b), "--diff"]) == 1
    err = capsys.readouterr().err
    assert "no step-aligned" in err


# ---------------------------------------------------------------------------
# spans inside the round: the u-step update's device scope, the program's
# host spans on the profiler's clock, and the compile counter
# ---------------------------------------------------------------------------
def _u_update_programs():
    loss_fn, mask, cu, cv = _quad(m=4)
    algo = _algo(loss_fn, mask)
    params = {"body": cu, "head": cv}
    b = _batches(cu, cv, 2)
    P = topology.ring(4)
    state, layout = algo.init_flat(params)
    prof = profiles.tiered(4, spread=3.0, push_delay_max=1, seed=1)
    rt, s_async = AsyncRuntime.build(algo, params, prof, depth=2)
    topo = topology.to_push_sparse(P)
    bt = {k: v[:, 0] for k, v in b["u"].items()}
    return {
        "resident": jax.jit(lambda s: algo.round_fn_flat(s, P, b, layout)
                            ).lower(state),
        "tree": jax.jit(lambda s: algo.round_fn(s, P, b)).lower(
            algo.init(params)),
        "async": jax.jit(lambda s: rt.tick(s, topo, bt)).lower(s_async),
    }


@pytest.mark.parametrize("path", ["resident", "tree", "async"])
def test_u_update_scope_names_the_update_in_the_compiled_round(path):
    """The SGD update of the shared part carries `dfedpgp.u_update` in
    every form of the step; in the resident round it nests in
    `dfedpgp.local`, the outer scope the benchmark splits the round by.
    (The async tick's compiled form may fuse the update under the
    phase select that follows it, so there the lowered program is read.)"""
    import re
    lowered = _u_update_programs()[path]
    if path == "async":
        assert dfedpgp.U_UPDATE_SCOPE in lowered.as_text(debug_info=True)
        return
    text = lowered.compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    ours = [v for v in names if dfedpgp.U_UPDATE_SCOPE in v]
    assert ours
    if path == "resident":
        assert all("/dfedpgp.local/" in v for v in ours)
        assert not any("dfedpgp.mix" in v for v in ours)


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData
    path, = Path(trace_dir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return {ev.name for plane in pd.planes for line in plane.lines
            for ev in line.events if ev.name.startswith("dfedpgp.host.")}


def test_host_spans_land_in_a_trace(tmp_path):
    sched = topology.TopologySchedule.random(8, 2, seed=3)
    t = obs.PhaseTimer()
    sched.at(0)                                   # compile outside
    with jax.profiler.trace(str(tmp_path)):
        with t.phase("round"):
            sched.at(1)
        with t.phase("fetch"):
            sched.induced(2, jnp.arange(4))
        with obs.span("dfedpgp.host.emit"):
            pass
    assert _host_span_names(tmp_path) == {
        "dfedpgp.host.topology", "dfedpgp.host.round",
        "dfedpgp.host.fetch", "dfedpgp.host.emit"}
    assert set(t.gauges()) == {"t_round_s", "t_fetch_s"}


def test_compile_count_counts_a_fresh_program_once():
    x, y = jnp.arange(5.0), jnp.arange(6.0)
    f = jax.jit(lambda x: jnp.cos(x) * 3.0 - x)
    before = obs.compile_count()
    f(x).block_until_ready()
    first = obs.compile_count()
    f(x).block_until_ready()
    assert first - before == 1
    assert obs.compile_count() == first
    # another shape is another program
    f(y).block_until_ready()
    assert obs.compile_count() == first + 1


def test_simulator_records_carry_compiles():
    from repro.fl.simulator import SimConfig, run_experiment
    from repro.spec import make_algo_spec

    spec = make_algo_spec("dfedpgp", topology="random", n_neighbors=2,
                          resident=True)
    sim = SimConfig(m=6, rounds=3, n_train=16, n_test=8, batch=8,
                    k_local=1, k_personal=1, spec=spec, seed=101)
    ring = obs.RingSink(16)
    run_experiment("dfedpgp", sim, eval_every=10, sink=ring)
    recs = [r for r in ring.records if r["kind"] == "round"]
    assert [r["step"] for r in recs] == [1, 2, 3]
    assert all(isinstance(r["compiles"], int) for r in recs)
    assert recs[0]["compiles"] >= 1               # the first round compiles
    assert recs[1]["compiles"] == 0               # the same programs again
    for r in recs:
        record.validate(r)


def test_train_records_split_round_and_fetch(tmp_path, monkeypatch):
    """launch/train.py: the round phase is the dispatch, the fetch is the
    round's one sync, and `round_s` is the two together."""
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    path = tmp_path / "m.jsonl"
    train.main(["--arch", "qwen2-0.5b", "--reduced", "--clients", "2",
                "--rounds", "2", "--batch", "1", "--seq", "8",
                "--layers", "1", "--resident", "--metrics", str(path)])
    recs = list(record.load_jsonl(str(path)))
    assert [r["step"] for r in recs] == [0, 1]
    for r in recs:
        assert {"t_data_s", "t_round_s", "t_fetch_s", "compiles"} <= set(r)
        assert "t_emit_s" not in r
        assert r["round_s"] == pytest.approx(r["t_round_s"] + r["t_fetch_s"],
                                             abs=2e-6)
    assert recs[0]["compiles"] >= 1
    assert recs[1]["compiles"] == 0
