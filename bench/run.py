"""Run one benchmark cell once and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits nonzero, with no result line, where JAX finds no TPU or fewer
chips than the cell asks for. See bench/README.md.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(sys.argv[1:], T_START))
