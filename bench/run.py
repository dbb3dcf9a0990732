"""Run one cell of the benchmark: ``python3 bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.

The last line of standard output is the result as one JSON object; the
numbers that decide ``correct`` are the last lines of standard error.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# JAX's persistent compilation cache goes where the program's
# ``compile_cache.configure()`` puts it by default, a fixed path inside the
# checkout, so that only a cell's first run there compiles and two checkouts
# share nothing: a cache directory set from outside is not followed.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
