"""Benchmark harness: one module per paper table (+ engine).

Prints a ``name,us_per_call,derived`` CSV after the human-readable tables;
``--json PATH`` additionally writes the rows as a machine-readable artifact
(CI uploads the engine suite's as BENCH_engine.json).

  PYTHONPATH=src python -m benchmarks.run [--only table1,engine]
"""

from __future__ import annotations

import argparse
import json
import sys

SUITES = ("table1", "table2", "superweight", "engine")


def write_rows_json(rows: list[tuple[str, float, str]], path: str) -> None:
    """Write ``(name, us_per_call, derived)`` rows as a JSON artifact."""
    with open(path, "w") as f:
        json.dump([{"name": n, "us_per_call": us, "derived": d}
                   for n, us, d in rows], f, indent=2)
    print(f"wrote {path}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help=f"comma-separated subset of {SUITES}")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as a JSON artifact")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(SUITES)

    rows: list[tuple[str, float, str]] = []
    if "table1" in only:
        from . import table1_memory
        rows += table1_memory.run()
    if "table2" in only:
        from . import table2_quality
        rows += table2_quality.run()
    if "superweight" in only:
        from . import superweight_ablation
        rows += superweight_ablation.run()
    if "engine" in only:
        from . import engine_bench
        rows += engine_bench.run()

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if args.json:
        write_rows_json(rows, args.json)


if __name__ == "__main__":
    main()
