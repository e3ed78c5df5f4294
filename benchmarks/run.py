"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

finds ``workloads/<name>.json``, the configuration and the traffic mix it
names, and the per-layer metrics by listing ``layer_metrics/``; runs the
cell on the chip this machine holds; prints one JSON object as its last
line.  Without a TPU, or with a device the peak table lacks, it exits 2
and prints no result.  ``--rehearse`` runs the same control flow at a toy
size on whatever backend JAX has, for the sandbox: it prints no rate,
time or share under any metric's name.
"""

from __future__ import annotations

import time
T_PROCESS_START = time.time()          # set-up counts from here

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    path = os.path.join(HERE, "workloads", name + ".json")
    if not os.path.isfile(path):
        known = sorted(f[:-5] for f in os.listdir(
            os.path.join(HERE, "workloads")) if f.endswith(".json"))
        raise SystemExit(f"no cell {name!r} under benchmarks/workloads "
                         f"(known: {known})")
    cell = load_json("workloads", name + ".json")
    cell["config_file"] = load_json("configs", cell["config"] + ".json")
    cell["traffic_file"] = load_json("traffic", cell["traffic"] + ".json")
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy size on any backend; prints no metric")
    ap.add_argument("--out", default=None,
                    help="directory for the raw trace (default: a "
                         "directory under TMPDIR, removed at exit)")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    if not os.path.isdir(os.path.join(ROOT, "lightgbm_tpu")):
        print("benchmarks/run.py: the program (lightgbm_tpu/) is not in "
              "this checkout; nothing to measure", file=sys.stderr)
        return 2

    from harness import device
    if args.rehearse:
        chip = device.rehearsal_device()
    else:
        try:
            chip = device.find_chip(int(cell["chips"]))
        except device.NoChip as e:
            print(f"benchmarks/run.py: {e}", file=sys.stderr)
            return 2

    kind = importlib.import_module("harness.kinds." + cell["kind"])
    return kind.run(cell, args, chip, T_PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
