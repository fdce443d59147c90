"""Run one benchmark cell on TPU chips.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell's configuration, traffic mix
and metrics are found by name in ``BENCHMARK.json``.  With ``--trace 0``
the result reports the cell's end-to-end metrics; with ``--trace 1`` it
records a profiler trace over a few seconds of the window and reports the
per-layer metrics, the device's busy time and a breakdown.  The last line
of standard output is the result as one JSON object; the numbers that
decide ``correct`` are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell needs, it exits non-zero and prints no
result.
"""
import time

STARTED = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[0] = str(_ROOT)                 # import chipbench as a package
sys.path.insert(1, str(_ROOT / "src"))   # and the program under test

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(started=STARTED))
