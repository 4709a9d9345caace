"""The benchmark's command: one cell of ``BENCHMARK.json``, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result; earlier lines say how the
set-up divides and how the window went. Exits non-zero, with no result,
without a TPU or with fewer chips than the cell asks for.
"""

import time

T0 = time.time()  # set-up is timed from here: before any heavy import

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmarks.lib.harness import main
    sys.exit(main(sys.argv[1:], ROOT, T0))
