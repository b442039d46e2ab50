"""Run one cell of the benchmark once, on the TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is an entry of `BENCHMARK.json`; its
configuration, traffic mix, driver, limits and per-layer readers are files
under `bench/` found by name.  A run:

1. finds the chips (a first device that is not a TPU, or fewer chips than
   the cell asks for, ends the run with exit code 2 and no result);
2. sets up: makes the data and the weights from the seed, builds the
   program's round, and drives its first rounds through the window's own
   call, taking the readings that the correctness check compares.  Warm
   rounds follow, so that every program the window runs is compiled;
3. measures: drives rounds for `--seconds`, ends in `block_until_ready`,
   and with `--trace 1` records the window with the profiler;
4. reads the peak device memory, frees the program's state, runs the plain
   reference over the same first rounds and compares;
5. prints the numbers compared beside their limits as the last lines of
   standard error, and one JSON object as the last line of standard output.

The persistent compilation cache is the program's fixed directory inside
the checkout (`repro.launch.compile_cache`), or `JAX_COMPILATION_CACHE_DIR`.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# the TPU runtime logs to a fixed /tmp directory unless told otherwise; a
# run writes nothing outside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / ".bench_out"          # traces (gitignored)
GIB = 2 ** 30


class NoChip(RuntimeError):
    """The run found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a cell needs, found by name from `BENCHMARK.json`."""
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    bench = root / "bench"
    cfg = load_json(bench / "configs" / f"{w['config']}.json")
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, name, e2e_names)]
    return {"name": name, "workload": w, "config": cfg, "traffic": traffic,
            "limits": load_json(bench / "limits" / f"{name}.json"),
            "end_to_end": e2e, "per_layer": per_layer,
            "driver": bench / "drivers" / f"{traffic['driver']}.py",
            "readers": {m["name"]: bench / "metrics" / f"{m['name']}.py"
                        for m in per_layer},
            "run_seconds": manifest["run_seconds"]}


def find_chips(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def peaks_of(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][kind]


def _compile_counter():
    """Counts the programs JAX compiles or loads from its cache (tracing
    alone, as eager `vmap` does on every call, is not counted)."""
    from jax import monitoring
    seen = {"n": 0}

    def on_event(event, *args, **kwargs):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_hits"):
            seen["n"] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    monitoring.register_event_listener(on_event)
    return seen


def _finite(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices: list, t0: float = T0) -> dict:
    """One run of a loaded cell on `devices`; returns the result object."""
    import jax

    from bench import correct

    compiles = _compile_counter()
    drv_mod = load_module(spec["driver"], f"bench_driver_{spec['name']}")
    drv = drv_mod.Driver(spec["config"], spec["traffic"], seed, devices)
    drv.setup()
    program = drv.readings.result()
    hlo = drv.lowered().compile().as_text() if trace else None
    # what set-up made stays out of the collector's way in the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0

    trace_dir = OUT / "trace" / spec["name"]
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    n0 = compiles["n"]
    rounds = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        start = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.round"):
                drv.step()
            rounds += 1
            if time.perf_counter() - start >= seconds:
                break
        drv.drain()
        window_s = time.perf_counter() - start
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles["n"] - n0

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    failed = drv.failed()
    work = drv.work(rounds)
    readers_ctx = None
    if trace:
        from bench import reduce
        red = reduce.reduce(reduce.find_trace(trace_dir), hlo,
                            [d.id for d in devices])
        readers_ctx = {"trace": red, "work": work, "chips": len(devices),
                       "peaks": spec["peaks"], "config": spec["config"],
                       "traffic": spec["traffic"],
                       "round_ops": drv.round_ops()}
    gc.unfreeze()
    drv.free()
    gc.collect()
    reference = drv.reference()
    numbers = correct.gaps(program, reference)
    ok, checks = correct.judge(numbers, spec["limits"])

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics = {}
    out = {"correct": bool(ok and failed == 0), "attempted": rounds,
           "failed": failed, "metrics": metrics, "device": device}
    if not trace:
        e2e = {"setup_s": setup_s, "peak_hbm_gib": peak / GIB}
        # a rate `<unit>_per_s` is the driver's count of that unit of work
        # (rounds, tokens) done in the window, over the window
        e2e.update({f"{unit}_per_s": n / window_s
                    for unit, n in work.items()})
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        red = readers_ctx["trace"]
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        for m in spec["per_layer"]:
            reader = load_module(spec["readers"][m["name"]],
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(readers_ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = red["breakdown"]
    out["checks"] = {n: {"value": _finite(c["value"]), "limit": c["limit"]}
                     for n, c in checks.items()}
    log = sys.stderr
    print(f"bench: {spec['name']} seed={seed} rounds={rounds} "
          f"window_s={window_s:.6f} setup_s={setup_s:.6f} "
          f"compiles_in_window={in_window} "
          f"worst_leaf={numbers['worst']} "
          f"left_out_of_change={numbers['left_out_of_change']}",
          file=log)
    print(f"bench: program={json.dumps(program)}", file=log)
    print(f"bench: reference={json.dumps(reference)}", file=log)
    for n, c in checks.items():
        print(f"check {n} {c['value']!r} limit {c['limit']!r}", file=log,
              flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program beside the benchmark ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    spec = load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # every program, however quick to compile, is kept: a later run of the
    # cell then loads all of them and its set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = find_chips(spec["workload"]["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    spec["peaks"] = peaks_of(devices[0].device_kind)
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
