"""Print digests of what a run builds from one seed, after the runner's
re-execution: the string hash the program's InitBuilder folds into each
weight key, and the weights themselves (at a small size, on the CPU)."""
import hashlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.run import _reexec  # noqa: E402

_reexec()

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench.reference import resnet50  # noqa: E402
from repro import configs  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.models.common import InitBuilder  # noqa: E402


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf, np.float32).tobytes())
    return h.hexdigest()


seed = int(sys.argv[1])
program = lm.build_params(configs.reduced("qwen3-1.7b"),
                          InitBuilder(jax.random.PRNGKey(seed)))
own = resnet50.make_weights(seed, num_classes=10)
print(hash("layer.attn.wq"), digest(program), digest(own))
