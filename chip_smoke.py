"""Chip smoke test: drive the DFedPGP main path once on a TPU.

Run from the root of the repository, in one process:

    python chip_smoke.py             # one chip: phases 1-5 below
    python chip_smoke.py --chips 4   # four chips: the Regime B mesh path only

Phases on one chip:

1. device check: the first device is a TPU (there is no CPU fallback);
2. Regime A, the paper's federation (m=100, 10 in-neighbours, Dirichlet
   alpha=0.3), 3 rounds with the compiled gossip_gather kernel
   (gossip="pallas") and with the XLA sparse mix; losses and final buffers
   agree to f32 tolerance;
3. the same federation at 25% participation, so each round writes back
   through the gossip_scatter kernel; finite, and the push-sum mass sums
   to m;
4. serving: a ServingState from phase 2's state serves 64 mixed users
   through the head_gather kernel, compared with the jnp reference;
5. Regime B: launch.train at qwen2-0.5b's published widths (2 clients,
   resident buffer), depth cut to what one chip's HBM holds.

With --chips 4: qwen2-0.5b at full width and depth, one client per chip
over a (4, 1) mesh, once with the ppermute mix and once with the matrix
mix, and the two runs' losses and buffers compared.

Every phase prints its config, compile and step seconds, losses, the
largest difference of each comparison, peak device memory, and whether
the compiled program that ran holds a tpu_custom_call for each kernel it
claims.  The
first failure exits non-zero.  The last line of standard output is one
JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import json
import math
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / ".smoke_out"          # per-run metrics (gitignored)
# qwen2-0.5b's 24 layers at 2 clients need 19.93 GiB of HBM by the TPU
# compiler's own count (RESOURCE_EXHAUSTED on a v5e's 15.75 GiB); the
# round plans 15.46 GiB at 16 layers, and each layer adds about 0.55
PHASE5_LAYERS = 16


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(ok: bool, msg: str):
    if not ok:
        fail(msg)


def log(phase: str, **kv):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def max_diff(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max()) if a.size else 0.0


def scale(a) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    return max(float(np.abs(a).max()) if a.size else 0.0, 1.0)


def compile_s(round_s: list) -> float:
    """First-round seconds less a steady round: the compile share."""
    return round_s[0] - sorted(round_s[1:])[len(round_s[1:]) // 2]


def kernels(lowered) -> set:
    """Names of the Pallas kernels compiled into `lowered`'s program as
    Mosaic calls (tpu_custom_call); interpret mode would leave none."""
    return {name for line in lowered.compile().as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for name in re.findall(r'/(\w+)/pallas_call"', line)}


def peak_memory(device=None) -> int:
    from repro import obs
    peak = obs.peak_device_memory(device)
    check(peak is not None, "the TPU reported no peak_bytes_in_use")
    return peak


def device_check(n_chips: int):
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log("device", platform=d0.platform, kind=repr(d0.device_kind),
        count=len(devs))
    check(d0.platform == "tpu", f"no TPU: JAX's first device is "
          f"{d0.platform!r}")
    check(len(devs) == n_chips, f"want {n_chips} chip(s); JAX sees "
          f"{len(devs)}")
    return devs


def regime_a(sim_kw: dict, tag: str):
    """One run_experiment; returns (history, seconds per round)."""
    from repro import obs
    from repro.fl.simulator import SimConfig, run_experiment
    sink = obs.RingSink()
    t0 = time.perf_counter()
    h = run_experiment("dfedpgp", SimConfig(rounds=3, **sim_kw),
                       eval_every=1, return_params=True, sink=sink)
    wall = time.perf_counter() - t0
    rs = [r["t_round_s"] for r in sink.records if r["kind"] == "round"]
    log(tag, config=sim_kw, rounds=3, wall_s=f"{wall:.3f}",
        compile_plus_round0_s=f"{rs[0]:.3f}", compile_s=f"{compile_s(rs):.3f}",
        step_s=[f"{x:.4f}" for x in rs[1:]],
        losses=[f"{x:.6f}" for x in h["loss"]], acc=h["final_acc"])
    return h


def phase_regime_a():
    """The two mixes agree to f32 rounding, on one input and over the
    3-round runs."""
    import jax
    import jax.numpy as jnp

    from repro.core import gossip
    from repro.spec import make_algo_spec
    hp = regime_a(dict(gossip="pallas"), "regimeA.pallas")
    hs = regime_a(dict(gossip="sparse"), "regimeA.sparse")
    sp, ss = hp["state"], hs["state"]
    P = make_algo_spec("dfedpgp", n_neighbors=10).schedule(
        sp.mu.shape[0]).at(3)
    mix = jax.jit(gossip.mix_flat, static_argnames=("mode",))
    fp, mp = mix(P, sp.flat, sp.mu, mode="pallas")
    fs, ms = mix(P, sp.flat, sp.mu, mode="sparse")
    d_mix, d_mix_mu = max_diff(fp, fs), max_diff(mp, ms)
    d_loss = max_diff(hp["loss"], hs["loss"])
    d_flat = max_diff(sp.flat, ss.flat)
    kern = "gossip_gather" in kernels(hp["round_lowered"])
    log("regimeA", m=sp.flat.shape[0], d_flat=sp.flat.shape[1],
        k=P.idx.shape[1], max_diff_mix=d_mix, max_diff_mix_mu=d_mix_mu,
        max_diff_loss=d_loss, max_diff_flat=d_flat,
        flat_scale=scale(ss.flat), gossip_gather_tpu_custom_call=kern,
        peak_bytes=peak_memory())
    check(kern, "the pallas round holds no gossip_gather tpu_custom_call")
    for h in (hp, hs):
        check(all(math.isfinite(x) for x in h["loss"]), "non-finite loss")
        check(bool(jnp.all(jnp.isfinite(h["state"].flat))),
              "non-finite buffer")
    check(d_mix <= 1e-6 * scale(fs), "pallas vs sparse mix of one buffer")
    check(d_mix_mu <= 1e-6, "pallas vs sparse mix of the weights")
    check(d_loss <= 1e-6 * scale(hs["loss"]), "pallas vs sparse losses")
    check(d_flat <= 1e-6 * scale(ss.flat), "pallas vs sparse final buffers")
    return hp


def phase_sampled():
    import jax.numpy as jnp

    h = regime_a(dict(gossip="pallas", participation="uniform",
                      participation_frac=0.25), "sampled")
    st = h["state"]
    m = st.mu.shape[0]
    kern = "gossip_scatter" in kernels(h["round_lowered"])
    mass = float(jnp.sum(st.mu))
    log("sampled", m=m, n_active=m // 4, mass=mass, mass_err=abs(mass - m),
        gossip_scatter_tpu_custom_call=kern, peak_bytes=peak_memory())
    check(kern, "the sampled round holds no gossip_scatter tpu_custom_call")
    check(all(math.isfinite(x) for x in h["loss"]), "non-finite loss")
    check(bool(jnp.all(jnp.isfinite(st.flat))), "non-finite buffer")
    check(abs(mass - m) <= 1e-4 * m, f"mass ledger {mass} != m={m}")


def phase_serve(hp):
    import jax
    import jax.numpy as jnp

    from repro import serve
    from repro.models import cnn
    cfg = cnn.CNNConfig()
    sstate = serve.from_train_state(hp["state"], layout=hp["layout"])
    B, m = 64, sstate.n_users()
    kx, ku = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(kx, (B, cfg.image_size, cfg.image_size,
                               cfg.channels))
    uid = jax.random.randint(ku, (B,), 0, m, jnp.int32)
    fused = jax.jit(lambda u, x: serve.serve_logits(sstate, u, x, cfg))
    ref = jax.jit(lambda u, x: serve.serve_logits(sstate, u, x, cfg,
                                                  force="ref"))
    t0 = time.perf_counter()
    got = fused(uid, x).block_until_ready()
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    got = fused(uid, x).block_until_ready()
    t_step = time.perf_counter() - t0
    want = ref(uid, x).block_until_ready()
    kern = "head_gather" in kernels(fused.lower(uid, x))
    d = max_diff(got, want)
    log("serve", users=m, batch=B, n_classes=cfg.n_classes,
        compile_plus_first_s=f"{t_first:.3f}", step_s=f"{t_step:.5f}",
        max_diff_fused_vs_ref=d, head_gather_tpu_custom_call=kern,
        peak_bytes=peak_memory())
    check(kern, "the fused serve program holds no head_gather "
          "tpu_custom_call")
    check(bool(jnp.all(jnp.isfinite(got))), "non-finite logits")
    # both sides may run their f32 matmul as one bf16 MXU pass
    check(d <= 2e-2 * scale(want), "fused vs ref serve logits")


def train_b(tag: str, argv: list):
    """launch.train.main in this process; returns (state, round records)."""
    from repro.launch import train
    OUT.mkdir(parents=True, exist_ok=True)
    path = OUT / f"{tag}.jsonl"
    path.unlink(missing_ok=True)
    argv = argv + ["--metrics", str(path)]
    log(tag, argv=" ".join(argv))
    t0 = time.perf_counter()
    state = train.main(argv)
    wall = time.perf_counter() - t0
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    recs = [r for r in recs if r["kind"] == "round"]
    losses = [r["loss"] for r in recs]
    rs = [r["round_s"] for r in recs]
    log(tag, wall_s=f"{wall:.3f}", compile_plus_round0_s=f"{rs[0]:.3f}",
        compile_s=f"{compile_s(rs):.3f}",
        step_s=[f"{x:.4f}" for x in rs[1:]],
        losses=[f"{x:.6f}" for x in losses],
        loss_v=[f"{r['loss_v']:.6f}" for r in recs])
    check(len(recs) == 3 and all(math.isfinite(x) for x in losses),
          f"{tag}: want 3 finite round losses, got {losses}")
    return state, recs


B_ARGS = ["--arch", "qwen2-0.5b", "--resident", "--rounds", "3",
          "--batch", "2", "--seq", "512"]


def phase_regime_b():
    from repro.configs import get_config
    cfg = get_config("qwen2-0.5b")
    log("regimeB", d_model=cfg.d_model, heads=f"{cfg.n_heads}/"
        f"{cfg.n_kv_heads}", d_ff=cfg.d_ff, vocab=cfg.vocab,
        layers=f"{PHASE5_LAYERS} of {cfg.n_layers} (depth cut: 2 clients "
        f"at full depth need 19.93 GiB of HBM, one v5e has 15.75)")
    state, _ = train_b("regimeB", B_ARGS + [
        "--clients", "2", "--layers", str(PHASE5_LAYERS)])
    log("regimeB", peak_bytes=peak_memory())
    del state


def phase_four_chips():
    import jax
    import numpy as np
    argv = B_ARGS + ["--clients", "4", "--topology", "exponential"]
    runs = {}
    for gossip in ("ppermute", "matrix"):
        state, recs = train_b(f"regimeB4.{gossip}",
                              argv + ["--gossip", gossip])
        flat = state.flat
        shards = sorted((s.device.id, s.data.shape)
                        for s in flat.addressable_shards)
        peaks = [peak_memory(d) for d in jax.devices()]
        log(f"regimeB4.{gossip}", flat_shape=flat.shape,
            flat_sharding=flat.sharding.spec, shards=shards,
            peak_bytes_per_device=peaks)
        check(len({dev for dev, _ in shards}) == 4 and all(
            shp[0] == 1 for _, shp in shards),
            f"{gossip}: the buffer is not one client row per chip")
        runs[gossip] = ([r["loss"] for r in recs], np.asarray(flat),
                        np.asarray(state.mu))
        del state, flat
    (lp, fp, mp), (lm, fm, mm) = runs["ppermute"], runs["matrix"]
    d_loss, d_mu = max_diff(lp, lm), max_diff(mp, mm)
    d_flat = max_diff(fp, fm)
    log("regimeB4", max_diff_loss=d_loss, max_diff_flat=d_flat,
        max_diff_mu=d_mu, flat_scale=scale(fm))
    check(d_loss <= 1e-6 * scale(lm), "ppermute vs matrix losses")
    check(d_flat <= 1e-6 * scale(fm), "ppermute vs matrix buffers")
    check(d_mu <= 1e-6, "ppermute vs matrix push-sum weights")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the four-chip Regime B mesh path")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"run from the repository root: no src/repro beside {__file__}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    log("cache", dir=use_compile_cache())
    devs = device_check(args.chips)
    if args.chips == 4:
        phase_four_chips()
    else:
        hp = phase_regime_a()
        phase_sampled()
        phase_serve(hp)
        del hp
        phase_regime_b()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
