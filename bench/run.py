"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
under ``bench/configs/`` and a traffic mix under ``bench/traffic/``; the
mix's ``kind`` names the driver module ``bench/drivers/<kind>.py``.  The
run loads, warms up every shape the window will use, measures for
``--seconds``, checks what the timed path produced against the plain
float32 reference, and prints one JSON object as its last line.

It exits non-zero, printing no result, where JAX finds no TPU, fewer
chips than the cell asks for, or a device missing from ``peaks.json``.
"""
import os
import sys
import time

# the set-up clock starts with the process; /proc gives its start time
START = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import main
    raise SystemExit(main(sys.argv[1:], root=ROOT, start=START))
