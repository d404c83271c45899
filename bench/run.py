"""Run one benchmark cell once and print its result as one JSON line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.  The persistent compilation cache is kept at
``.jax_cache`` in the checkout.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_BENCH = Path(__file__).resolve().parent
_ROOT = _BENCH.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(_ROOT / ".jax_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
sys.path[:0] = [str(_BENCH), str(_ROOT / "src")]

from benchkit import runner  # noqa: E402

if __name__ == "__main__":
    sys.exit(runner.main(t_start=T_START))
