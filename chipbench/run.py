"""One run of one benchmark cell; see ``harness.py``.

    python3 chipbench/run.py --workload vitb32.step --seed 7 --seconds 30 \
        --trace 0
"""
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T0))
