"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Refuses to run (exit 2, no result) unless JAX's first device is a TPU and
there are as many chips as the cell asks for. The last line of standard
output is the result as one JSON object; the numbers the comparison used
follow on standard error, each beside its limit.
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
