#!/usr/bin/env python3
"""The lower-precision control of a cell, which has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed, at the cell's own size: the plain reference computed in
bfloat16 (the precision below the float32 the configuration states) is put
in the program's place, and its answers to every query of the cell's mix are
compared with the float32 reference's by the comparison that decides
``correct``. Prints one JSON line per seed with the numbers beside their
limits; exits 1 if any seed's control passes. The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from benchlib import catalog, checks, reference, traffic  # noqa: E402


def control_numbers(cfg: dict, mix: dict, seed: int) -> dict:
    xyz = catalog.make_catalog(cfg, seed)
    qs = traffic.queries(cfg, mix["mix"])
    want = reference.reference_answers(xyz, qs)
    got = reference.reference_answers(xyz, qs, dtype="bfloat16")
    return checks.compare(got, want)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import run
    _, cell, cfg, mix = run.load_cell(args.workload)
    run.use_cache()
    ok = True
    for seed in args.seeds:
        numbers = control_numbers(cfg, mix, seed)
        passed = checks.passed(numbers)
        ok &= not passed
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "control_correct": passed, "checks": numbers}),
              flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
