import os
os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST run before any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers, compiles, and fits — without TPU hardware.

For each combo this script:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. builds the step function (train_step / prefill_step / serve_step) with
     explicit in/out shardings from the layout rules,
  3. ``jax.jit(...).lower(*ShapeDtypeStructs).compile()`` — no allocation,
  4. records ``memory_analysis()`` (fits?), ``cost_analysis()`` (FLOPs/bytes
     for the roofline) and the collective bytes parsed from the
     post-SPMD compiled HLO,
  5. writes one JSON per combo into benchmarks/artifacts/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single [--gossip matrix|ppermute] [--k_u 1]
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every combo
"""
import argparse
import json
import re
import sys
import time
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# wire-byte convention per collective (documented in EXPERIMENTS.md §Roofline):
#   all-reduce      2 x out   (ring reduce-scatter + all-gather)
#   all-gather      1 x out   (each device receives out*(n-1)/n ~ out)
#   reduce-scatter  1 x in    (each device ships its full input once)
#   all-to-all      1 x out
#   collective-permute 1 x out
_SHAPE_RE = re.compile(r"(pred|[sufb]\w*\d+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\((.*)$")


def parse_collectives(hlo_text: str) -> dict:
    """Sum wire bytes of every collective op in post-SPMD HLO."""
    out = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        out_ty, op, is_start, args = m.groups()
        out_shapes = _SHAPE_RE.findall(out_ty)
        out_b = sum(_shape_bytes(d, s) for d, s in out_shapes)
        in_b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(args))
        if is_start:           # async start: output tuple carries in+out
            out_b = max(out_b - in_b, out_b // 2)
        if op == "all-reduce":
            b = 2 * out_b
        elif op == "reduce-scatter":
            b = in_b or out_b
        else:
            b = out_b
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += int(b)
    return out


def run_one(arch: str, shape_name: str, mesh_kind: str, gossip: str = "matrix",
            k_u: int = 1, k_v: int = 1, save: bool = True,
            keep_hlo: bool = False, unroll: bool = False,
            bf16_grads: bool = False, kv_quant: bool = False,
            bf16_params: bool = False, moe_shard: str = "",
            gossip_dtype: str = "", resident: bool = False,
            topology_kind: str = "", n_neighbors: int = 10,
            tag: str = "") -> dict:
    import jax
    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.core import topology
    from repro.launch import steps
    from repro.launch.mesh import make_production_mesh

    shape = SHAPES[shape_name]
    if not shape_applicable(arch, shape_name):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped",
                "reason": "full-attention arch; long_500k needs sub-quadratic "
                          "attention (DESIGN.md §4)"}

    cfg = get_config(arch)
    if unroll:
        # unroll the layer scans so cost_analysis counts EVERY layer
        # (a rolled while-body is costed once); exact roofline numbers.
        cfg = cfg.replace(scan_unroll=max(cfg.n_layers, cfg.n_enc_layers, 2))
    if kv_quant:
        cfg = cfg.replace(kv_quant=True)
    if bf16_params:
        cfg = cfg.replace(param_dtype="bfloat16")
    if moe_shard:
        cfg = cfg.replace(moe_dispatch_axes=tuple(moe_shard.split(",")))
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    layout = steps.decide_layout(mesh, arch, shape)
    schedule = None
    if topology_kind:
        # the run's ONE TopologySchedule, threaded through build_step into
        # the mix (docs/gossip.md §One topology object)
        n = n_neighbors if topology_kind == "random" else 0
        schedule = topology.TopologySchedule(topology_kind,
                                             layout.n_clients, n)
    kw = dict(k_u=k_u, k_v=k_v, gossip=gossip, bf16_grads=bf16_grads,
              gossip_dtype=gossip_dtype, schedule=schedule,
              resident=resident) if shape.kind == "train" else {}

    t0 = time.time()
    fn, ins, outs, args, donate = steps.build_step(cfg, mesh, layout, shape,
                                                   **kw)
    with jax.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "gossip": gossip, "status": "ok", "unroll": bool(unroll),
        "resident": bool(resident), "topology": topology_kind,
        "bf16_grads": bool(bf16_grads), "kv_quant": bool(kv_quant),
        "layout": {"client_axes": layout.client_axes,
                   "batch_axes": layout.batch_axes,
                   "tp_axes": layout.tp_axes,
                   "fsdp_axes": layout.fsdp_axes,
                   "n_clients": layout.n_clients,
                   "per_client_batch": layout.per_client_batch},
        "k_u": k_u, "k_v": k_v,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }

    try:
        ma = compiled.memory_analysis()
        rec["memory_analysis"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes") if hasattr(ma, k)}
    except Exception as e:  # CPU backend may not expose it
        rec["memory_analysis"] = {"error": str(e)}

    try:
        ca = compiled.cost_analysis()
        rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                                if isinstance(v, (int, float))
                                and "{" not in k}
    except Exception as e:
        rec["cost_analysis"] = {"error": str(e)}

    hlo = compiled.as_text()
    rec["collectives"] = parse_collectives(hlo)
    rec["hlo_ops"] = {op: hlo.count(f" {op}(")
                      for op in ("all-gather", "all-reduce", "reduce-scatter",
                                 "all-to-all", "collective-permute", "fusion",
                                 "while", "dot", "custom-call")}
    rec["hlo_chars"] = len(hlo)

    # analytic per-device parameter bytes from the actual shardings
    from repro.launch.steps import params_shardings, stacked_param_struct
    ps_struct = stacked_param_struct(cfg, layout.n_clients)
    ps_shard = params_shardings(ps_struct, mesh, layout)
    ndev = mesh.devices.size
    pb = 0
    for leaf, sh in zip(jax.tree.leaves(ps_struct), jax.tree.leaves(ps_shard)):
        n_shards = 1
        for ax in jax.tree.leaves(tuple(sh.spec)):
            if ax is not None:
                n_shards *= mesh.shape[ax]
        pb += leaf.size * leaf.dtype.itemsize // n_shards
    rec["param_bytes_per_device"] = int(pb)
    rec["n_devices"] = int(ndev)

    if keep_hlo:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        (ARTIFACTS / f"{arch}__{shape_name}__{mesh_kind}__{gossip}.hlo.txt"
         ).write_text(hlo)
    if save:
        ARTIFACTS.mkdir(parents=True, exist_ok=True)
        sfx = ("__unroll" if unroll else "") + (f"__{tag}" if tag else "")
        out = ARTIFACTS / f"{arch}__{shape_name}__{mesh_kind}__{gossip}{sfx}.json"
        out.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--gossip", default="matrix",
                    choices=["matrix", "ppermute"])
    ap.add_argument("--k_u", type=int, default=1)
    ap.add_argument("--k_v", type=int, default=1)
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer scans for exact cost_analysis")
    ap.add_argument("--bf16-grads", action="store_true")
    ap.add_argument("--bf16-params", action="store_true")
    ap.add_argument("--moe-shard", default="",
                    help="expert,token mesh axes for the dispatch buffer")
    ap.add_argument("--gossip-dtype", default="",
                    help="bfloat16 = quantized push-sum payload")
    ap.add_argument("--resident", action="store_true",
                    help="resident flat-buffer train step "
                         "(FlatDFedPGPState carry)")
    ap.add_argument("--topology", default="", dest="topology_kind",
                    choices=["", "random", "exponential", "ring", "full"],
                    help="thread a TopologySchedule of this kind through "
                         "the step builder (default: legacy dense P arg)")
    ap.add_argument("--neighbors", type=int, default=10,
                    help="in-degree for --topology random (paper: 10)")
    ap.add_argument("--kv-quant", action="store_true")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) on this mesh")
    args = ap.parse_args(argv)

    from repro.configs import ARCH_IDS, SHAPES

    combos = ([(a, s) for a in ARCH_IDS for s in SHAPES]
              if args.all else [(args.arch, args.shape)])
    failed = 0
    for arch, shp in combos:
        try:
            rec = run_one(arch, shp, args.mesh, gossip=args.gossip,
                          k_u=args.k_u, k_v=args.k_v,
                          keep_hlo=args.keep_hlo, unroll=args.unroll,
                          bf16_grads=args.bf16_grads, kv_quant=args.kv_quant,
                          bf16_params=args.bf16_params,
                          moe_shard=args.moe_shard,
                          gossip_dtype=args.gossip_dtype,
                          resident=args.resident,
                          topology_kind=args.topology_kind,
                          n_neighbors=args.neighbors, tag=args.tag)
            status = rec["status"]
            extra = ""
            if status == "ok":
                f = rec["cost_analysis"].get("flops", float("nan"))
                extra = (f" compile={rec['compile_s']}s flops={f:.3e}"
                         f" colls="
                         f"{sum(v['bytes'] for v in rec['collectives'].values()):.3e}B")
            print(f"[dryrun] {arch:22s} {shp:12s} {args.mesh:6s} {status}{extra}",
                  flush=True)
        except Exception as e:
            failed += 1
            print(f"[dryrun] {arch:22s} {shp:12s} {args.mesh:6s} "
                  f"FAILED: {type(e).__name__}: {e}", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
