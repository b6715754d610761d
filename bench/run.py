"""Runs one benchmark cell and prints its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; see
bench/harness/runner.py for how its files are found.  Exits non-zero,
with no result line, when JAX finds no TPU or fewer chips than the cell
asks for.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
