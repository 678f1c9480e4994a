"""Without a TPU the runner exits non-zero and prints no result line."""
import os
import subprocess
import sys
from pathlib import Path

RUN = Path(__file__).parent / "run.py"


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(RUN), "--workload",
         "resnet50.paper_split.poisson", "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr
