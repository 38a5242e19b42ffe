#!/usr/bin/env python3
"""The benchmark's one command:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on; the
last line of standard output is the result. See perfbench/README.md.
"""

import sys
import time

T_PROCESS = time.time()

if __name__ == "__main__":
    import harness

    sys.exit(harness.main(t_process=T_PROCESS))
