"""Two processes started through the runner's re-execution build the
same weights from one seed, also where the program keys weights by
Python's string hash."""
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = Path(__file__).parent / "testdata" / "weights_digest.py"


def _run(seed: int, hash_seed: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONHASHSEED=hash_seed)
    out = subprocess.run([sys.executable, str(SCRIPT), str(seed)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_same_seed_same_weights_across_processes():
    a = _run(1234567890123, "random")
    b = _run(1234567890123, "12345")
    assert a == b
    c = _run(7, "random")
    assert c.split()[1:] != a.split()[1:]
