"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, builds the system under test
from the seed, warms up every shape the cell uses, measures for
``--seconds``, checks the timed path's output against the plain
reference, and prints one JSON object as the last line of stdout.  Exits
2, printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import core

    sys.exit(core.main(t_start=T_START))
