"""Drives a whole run with the timed path broken underneath: every float
the server renders is altered where it is produced (server/pgwire.py's
`_render`), a sum off in its seventh digit, which is what a lower-precision
aggregate gives. Started by test_broken_path.py in a process of its own.

    python benchmarks/tests/broken_run.py <run.py arguments>
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

if __name__ == "__main__":
    import run as bench_run
    from cockroach_tpu.server import pgwire

    render = pgwire._render
    pgwire._render = lambda v: render(
        v * (1 + 1e-6) if isinstance(v, float) else v)
    sys.exit(bench_run.main(sys.argv[1:]))
