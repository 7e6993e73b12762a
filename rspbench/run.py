"""On-chip benchmark of the RSP system: runs one cell once.

    python3 rspbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds the program (``src/``) and this
directory.  Set-up (corpus from the seed, partition, store, warm-up of every
shape the cell's traffic uses) is timed as ``setup_s``; then the cell's
traffic runs for ``--seconds``; then what the window produced is compared
with a plain reference.  The last line of standard output is the result
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``; with
``--trace 1`` the per-layer metrics and a ``breakdown`` of the device
trace), and the last lines of standard error give each compared number
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it exits non-zero and prints no result.
"""

import time

_T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=_T0))
