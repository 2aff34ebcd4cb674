"""Benchmark entry point; see ``harness.py``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from chipbench import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
