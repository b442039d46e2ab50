"""Chip benchmark of the DFedPGP engine.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once on the TPU and prints one JSON line.
Everything a cell needs is found by name: its configuration in
`configs/<config>.json`, its traffic mix in `traffic/<traffic>.json` (which
names the driver in `drivers/<driver>.py`), the limits of its correctness
check in `limits/<cell>.json`, and each per-layer metric's reader in
`metrics/<metric>.py`.
"""
