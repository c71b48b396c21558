#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run of one cell on the chip it is started on; the last
line of standard output is the result object. Exits non-zero, with no
result, without a TPU or without the program beside it.
"""

import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness import cell

    # the process environment is the configuration's (its ``env`` key),
    # set before the program and pyarrow load
    cell.apply_env(sys.argv[1:])
    from benchmark.harness import runner

    sys.exit(runner.main(sys.argv[1:], T_START))
