"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a model configuration and a traffic mix (``BENCHMARK.json``).
The run builds the model from the seed, warms up, serves the traffic for
``--seconds`` seconds, checks the served logits against the plain reference
on the host CPU, and prints one JSON object as the last line of stdout.
It exits non-zero, printing no result, where JAX finds no accelerator.
"""

import os
import sys
import time

T_START = time.monotonic()  # set-up is timed from here to the window

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.lib import env  # noqa: E402  (sets JAX's environment first)

env.prepare()

from bench.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t_start=T_START))
