import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY = os.path.join(HERE, "manifest_tiny.json")


def run_cell(workload, seed=11, seconds=3, trace=0, manifest=TINY,
             run_py=None, extra=()):
    """The cell's command end to end, as the driver would start it, held
    to the CPU. -> (exit code, parsed stdout lines, stderr)."""
    e = dict(os.environ, JAX_PLATFORMS="cpu",
             PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, run_py or os.path.join(BENCH, "run.py"),
         "--manifest", manifest, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), *extra],
        capture_output=True, text=True, timeout=900, env=e, cwd=ROOT)
    lines = []
    for ln in p.stdout.splitlines():
        try:
            obj = json.loads(ln)
        except ValueError:
            continue
        if isinstance(obj, dict) and "sev" not in obj:
            lines.append(obj)
    return p.returncode, lines, p.stderr
