"""Readings that a cell's correctness limits are set from (run on the chip).

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--faulted 3] [--out chiprun_out/calibrate]

In one process, for each seed: the program's set-up rounds and its readings
against the plain reference (the lower readings).  For the first
`--faulted` seeds also the control, the reference at fp8 products put in
the program's place, and the faults the check has to catch, planted in the
reference put in the program's place: half of each batch left out, and the
mix left out.  A state left unchanged reads 1 on the leaf gaps by their
measure and needs no run.  One JSON line per seed goes to standard output
and to `<out>/<cell>.jsonl`.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faulted", type=int, default=3)
    ap.add_argument("--out", default="chiprun_out/calibrate")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import correct, run
    spec = run.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devices = run.find_chips(spec["workload"]["chips"])
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    out_dir = ROOT / args.out
    out_dir.mkdir(parents=True, exist_ok=True)
    sink = open(out_dir / f"{args.workload}.jsonl", "a")
    drv_mod = run.load_module(spec["driver"], "bench_driver")
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        drv = drv_mod.Driver(spec["config"], spec["traffic"], seed, devices)
        drv.setup()
        program = drv.readings.result()
        setup_s = time.perf_counter() - t0
        drv.free()
        gc.collect()
        t1 = time.perf_counter()
        ref = drv.reference()
        ref_s = time.perf_counter() - t1
        rec = {"seed": seed, "setup_s": setup_s, "reference_s": ref_s,
               "program_readings": program, "reference": ref,
               "program": correct.gaps(program, ref)}
        if i < args.faulted:
            rec["control"] = correct.gaps(drv.reference(precision="fp8"),
                                          ref)
            for fault in ("half_batch", "no_mix"):
                rec[fault] = correct.gaps(drv.reference(fault=fault), ref)
        rec["peak_bytes"] = max((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0) for d in devices)
        line = json.dumps(rec)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()
        del drv
        gc.collect()
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
