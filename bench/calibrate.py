"""Readings that the limits of ``cells/<cell>.json`` are set from.

  python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

Runs the cell once per seed in one process (a short window at the cell's
own load, the comparison, and the control over the same positions) and
prints, per seed, each statistic of ``benchkit.check.STATS`` for the
served tokens and for the control's.  A limit lies above the program's
readings over a dozen seeds or more and below the control's, with the
more room above the program's.  Writes the readings as JSON to ``--out``
when given.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_BENCH = Path(__file__).resolve().parent
_ROOT = _BENCH.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_ROOT / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
sys.path[:0] = [str(_BENCH), str(_ROOT / "src")]

from benchkit import check, runner  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    rows = []
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = argparse.Namespace(workload=a.workload, seed=seed,
                                  seconds=a.seconds, trace=0)
        t = time.perf_counter()
        res = runner.run(args, t_start=t, control=True)
        row = {"seed": seed, **res["readings"], "failed": res["failed"],
               "attempted": res["attempted"],
               "out_tok_s": res["metrics"].get("out_tok_s", {}).get("value"),
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": a.workload, "seeds": len(rows)}
    for name in check.STATS:
        lo = max(r[name]["program"] for r in rows)
        hi = min(r[name]["control"] for r in rows)
        summary[name] = {"program_max": lo, "control_min": hi,
                         "ratio": hi / lo if lo > 0 else float("inf")}
    print(json.dumps(summary))
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps({"rows": rows,
                                           "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
