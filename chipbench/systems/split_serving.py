"""A CNN split into stages behind the multi-tenant gateway, on one chip.

The system under test is the program's serving path as a user deploys
it: ``Gateway`` -> ``Session`` -> ``EdgePipeline`` on the thread engine,
every stage on the chip, the hops given as links that cost nothing, so
no emulation sleeps.  The cell file gives the cuts, the codec, the
backend and the gateway's batch; the configuration gives the model.

The configuration names the model's plain reference
(``chipbench/reference/<reference>.py``).  Weights are the reference's
own, from the seed, handed to the program in its layout; the reference
also gives the FLOPs charged per image (``flops_per_item``).  ``check``
runs the plain reference over every image the traffic used and compares
every delivered row.
"""
from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np

# A link that costs nothing: zero round trip, unbounded bandwidth, so
# EmulatedChannel's modelled wire time is exactly 0 on every hop.
FREE_LINK = dict(rtt_s=0.0, bw_bytes_per_s=math.inf)


def reference(config: dict):
    """The module ``chipbench/reference/<config["reference"]>.py``."""
    return importlib.import_module(f"chipbench.reference.{config['reference']}")


class System:
    """The deployment, built and warmed: ``submit``/``poll`` are the
    gateway's own calls, the only entries the window drives."""

    def __init__(self, config: dict, cell: dict, traffic: dict, seed: int):
        from repro.core.devices import Link
        from repro.core.scenarios import TenantSpec
        from repro.models.cnn import zoo
        from repro.runtime import EdgePipeline, Gateway

        self.config, self.cell = config, cell
        self.hw = int(config["image_size"])
        self.num_classes = int(config["num_classes"])
        self.max_batch = int(cell["max_batch"])
        self.ref = reference(config)
        self.flops_per_item = self.ref.flops_per_image(self.hw,
                                                       self.num_classes)
        model = zoo.get(config["zoo_name"], num_classes=self.num_classes)
        self.weights = self.ref.make_weights(seed, self.num_classes)
        params = self.ref.program_params(self.weights)
        want = jax.tree.structure(jax.eval_shape(model.init,
                                                 jax.random.PRNGKey(0)))
        if jax.tree.structure(params) != want:
            raise RuntimeError("the program's resnet layout changed: "
                               f"{want} vs {jax.tree.structure(params)}")
        links = [Link(f"free{i}", **FREE_LINK)
                 for i in range(len(cell["cuts"]))]
        self.pipe = EdgePipeline(model, params, tuple(cell["cuts"]), links,
                                 backend=cell["backend"], codec=cell["codec"])
        self.assert_nothing_emulated()
        self.pipe.warmup(np.zeros((self.max_batch, self.hw, self.hw, 3),
                                  np.float32))
        tenants = [TenantSpec(f"tenant{i}", slo_s=float(traffic["slo_s"]))
                   for i in range(int(traffic["tenants"]))]
        self.tenant_names = [t.name for t in tenants]
        self.gateway = Gateway(self.pipe, tenants, max_batch=self.max_batch)

    # -- what the window drives ----------------------------------------- #
    def submit(self, tenant: str, x) -> int:
        return self.gateway.submit(tenant, x)

    def poll(self, block: bool):
        return self.gateway.poll(block=block)

    @property
    def pending(self) -> int:
        return self.gateway.pending

    @property
    def in_flight(self) -> int:
        return self.gateway.session.outstanding

    @property
    def batch_window_s(self) -> float:
        return self.gateway.batch_window_s

    def epoch(self) -> float:
        """perf_counter() at the pipeline clock's zero (QoSRecord.t_s)."""
        return self.pipe.epoch

    def drain_qos(self):
        return self.gateway.drain_qos()

    # -- guards ---------------------------------------------------------- #
    def assert_nothing_emulated(self) -> None:
        """Fail the run if any emulation would run: a modelled wire
        time, a stage pace, or a backend other than ``lightweight``."""
        pipe = self.pipe
        wire = sum(n.total_elapsed_s for n in pipe.nets)
        bad = []
        if wire != 0.0:
            bad.append(f"modelled wire time {wire} s")
        if any(p != 0.0 for p in pipe.stage_pace_s):
            bad.append(f"stage paces {pipe.stage_pace_s}")
        if any(b != "lightweight" for b in pipe.backends):
            bad.append(f"backends {pipe.backends}")
        if any(not (l.rtt_s == 0.0 and math.isinf(l.bw_bytes_per_s))
               for l in pipe.links):
            bad.append("a link with a cost")
        if bad:
            raise RuntimeError("emulation in a chip cell: " + "; ".join(bad))

    def close(self) -> None:
        """End the gateway and the pipeline and free their device
        buffers, so that the reference runs on an empty chip."""
        gw, pipe = self.gateway, self.pipe
        self.gateway = self.pipe = None
        try:
            gw.close()
        finally:
            pipe.close()

    # -- correctness ------------------------------------------------------ #
    def check(self, pool: np.ndarray, answers, limits: dict) -> list:
        """Every delivered row against the reference on the same image."""
        refs = reference_outputs(self.ref, self.weights, pool,
                                 self.config["matmul_precision"],
                                 block=self.max_batch)
        return compare_rows(refs, answers, limits)


def reference_outputs(ref, weights: dict, images: np.ndarray,
                      precision: str, block: int = 8,
                      dtype=jnp.float32) -> np.ndarray:
    """The plain reference over ``images``, in blocks of rows, in
    ``dtype`` with matmul ``precision`` (``default`` is what the chip
    does unasked: float32 products in one bfloat16 pass, accumulated in
    float32)."""
    w = jax.tree.map(lambda a: a.astype(dtype), weights)

    def fwd(w, x):
        with jax.default_matmul_precision(precision):
            return ref.forward(w, x.astype(dtype)).astype(jnp.float32)
    fn = jax.jit(fwd)
    out = []
    for i in range(0, len(images), block):
        x = images[i:i + block]
        n = len(x)
        if n < block:                      # one compiled shape
            x = np.concatenate([x, np.zeros((block - n,) + x.shape[1:],
                                            x.dtype)])
        out.append(np.asarray(fn(w, x))[:n])
    return np.concatenate(out)


def compare_rows(refs: np.ndarray, answers, limits: dict) -> list:
    """``answers``: (first image, delivered rows) per request.  The
    number compared is the worst row's largest error over that row's
    largest reference logit; a missing or malformed row reads +inf.
    → [(name, reading, limit)]."""
    worst = 0.0
    for idx, y in answers:
        r = refs[idx:idx + len(y)]
        if y.shape != r.shape or not np.isfinite(y).all():
            worst = math.inf
            break
        scale = np.max(np.abs(r), axis=1)
        err = np.max(np.abs(y - r), axis=1) / np.maximum(scale, 1e-30)
        worst = max(worst, float(err.max()))
    return [("row_rel_err", worst, limits["row_rel_err"])]


def control_reading(config: dict, seed: int, images: np.ndarray,
                    limits: dict) -> list:
    """The control in the program's place: the reference computed in
    bfloat16, judged as the program is."""
    ref, precision = reference(config), config["matmul_precision"]
    w = ref.make_weights(seed, int(config["num_classes"]))
    ctrl = reference_outputs(ref, w, images, precision, dtype=jnp.bfloat16)
    return compare_rows(reference_outputs(ref, w, images, precision),
                        [(i, ctrl[i:i + 1]) for i in range(len(images))],
                        limits)
