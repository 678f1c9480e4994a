"""A plain ResNet-50 (He et al., arXiv:1512.03385; torchvision layout,
stride on the 3x3), inference mode, NHWC, written for the benchmark
alone.  It imports nothing of the program.

``make_weights`` builds the benchmark's weights from a seed on the
device, in one jitted call, under torchvision's parameter names.  The
program gets the same arrays in its own block order
(``program_params``); the reference reads them as they are.
``flops_per_image`` is what the benchmark charges one image.

A serving configuration names its reference module (``"reference"``);
another model brings a module with the same four functions.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import flops

STAGES = flops.RESNET50_STAGES
BN_EPS = 1e-5


def flops_per_image(hw: int, num_classes: int) -> float:
    return flops.resnet_flops_per_image(hw, num_classes, STAGES)


def layout(num_classes: int = 1000) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of every weight, in a fixed order.  Convolutions
    are HWIO; batch norms have weight, bias, running mean and var."""
    out: list[tuple[str, tuple[int, ...]]] = []

    def conv(name, k, cin, cout):
        out.append((f"{name}.weight", (k, k, cin, cout)))

    def bn(name, c):
        for p in ("weight", "bias", "running_mean", "running_var"):
            out.append((f"{name}.{p}", (c,)))

    conv("conv1", 7, 3, 64)
    bn("bn1", 64)
    cin = 64
    for si, (mid, blocks, stride) in enumerate(STAGES):
        cout = 4 * mid
        for j in range(blocks):
            pre = f"layer{si + 1}.{j}"
            conv(f"{pre}.conv1", 1, cin, mid)
            bn(f"{pre}.bn1", mid)
            conv(f"{pre}.conv2", 3, mid, mid)
            bn(f"{pre}.bn2", mid)
            conv(f"{pre}.conv3", 1, mid, cout)
            bn(f"{pre}.bn3", cout)
            if j == 0 and (stride != 1 or cin != cout):
                conv(f"{pre}.downsample.0", 1, cin, cout)
                bn(f"{pre}.downsample.1", cout)
            cin = cout
    out.append(("fc.weight", (cin, num_classes)))
    out.append(("fc.bias", (num_classes,)))
    return out


def program_params(w: dict) -> list:
    """The same arrays in the program's block order (``zoo.resnet50``):
    conv1, bn1, relu, maxpool, sixteen bottlenecks, avgpool, fc.  It
    moves arrays and changes no number."""
    def bn(n):
        return {"scale": w[f"{n}.weight"], "bias": w[f"{n}.bias"],
                "mean": w[f"{n}.running_mean"], "var": w[f"{n}.running_var"]}

    params: list = [{"w": w["conv1.weight"]}, bn("bn1"), {}, {}]
    for si, (_, blocks, _) in enumerate(STAGES):
        for j in range(blocks):
            pre = f"layer{si + 1}.{j}"
            body = [{"w": w[f"{pre}.conv1.weight"]}, bn(f"{pre}.bn1"), {},
                    {"w": w[f"{pre}.conv2.weight"]}, bn(f"{pre}.bn2"), {},
                    {"w": w[f"{pre}.conv3.weight"]}, bn(f"{pre}.bn3")]
            short = ([{"w": w[f"{pre}.downsample.0.weight"]},
                      bn(f"{pre}.downsample.1")]
                     if f"{pre}.downsample.0.weight" in w else {})
            params.append({"body": body, "short": short})
    params.append([{}, {}])
    params.append({"w": w["fc.weight"], "b": w["fc.bias"]})
    return params


def make_weights(seed: int, num_classes: int = 1000) -> dict:
    """Weights from ``seed``, made on the default device in one call.

    Convolutions are He-normal; batch norms get scales near 1, shifts,
    running means and variances near their neutral values, so that
    every term of the inference-mode batch norm is exercised.  The last
    batch norm of each residual branch is scaled by 0.5 so that
    activations stay of order one through sixteen residual adds."""
    names = layout(num_classes)

    def build(key):
        w = {}
        for i, (name, shape) in enumerate(names):
            k = jax.random.fold_in(key, i)
            n = jax.random.normal(k, shape, jnp.float32)
            leaf = name.rsplit(".", 1)[1]
            if len(shape) == 4:
                fan_in = shape[0] * shape[1] * shape[2]
                w[name] = n * math.sqrt(2.0 / fan_in)
            elif name == "fc.weight":
                w[name] = n / math.sqrt(shape[0])
            elif leaf == "weight":
                gain = 0.5 if name.endswith("bn3.weight") else 1.0
                w[name] = gain * (1.0 + 0.1 * n)
            elif leaf == "running_var":
                w[name] = 1.0 + 0.1 * jnp.abs(n)
            else:                         # bias, running_mean, fc.bias
                w[name] = 0.1 * n
        return w
    return jax.jit(build)(jax.random.PRNGKey(seed))


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, w, name):
    """Inference batch norm, as one scale and one shift per channel."""
    scale = jax.lax.rsqrt(w[f"{name}.running_var"] + BN_EPS) \
        * w[f"{name}.weight"]
    return x * scale + (w[f"{name}.bias"] - w[f"{name}.running_mean"] * scale)


def forward(w: dict, x):
    """Logits of the images ``x`` (N, H, W, 3) under weights ``w``.
    Computes in the dtype of ``x`` and of the weights it is given."""
    def relu(t):
        return jnp.maximum(t, 0)
    x = relu(_bn(_conv(x, w["conv1.weight"], 2, 3), w, "bn1"))
    x = lax.reduce_window(x, jnp.array(-jnp.inf, x.dtype), lax.max,
                          (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for si, (_, blocks, stride) in enumerate(STAGES):
        for j in range(blocks):
            pre = f"layer{si + 1}.{j}"
            s = stride if j == 0 else 1
            y = relu(_bn(_conv(x, w[f"{pre}.conv1.weight"], 1, 0), w,
                         f"{pre}.bn1"))
            y = relu(_bn(_conv(y, w[f"{pre}.conv2.weight"], s, 1), w,
                         f"{pre}.bn2"))
            y = _bn(_conv(y, w[f"{pre}.conv3.weight"], 1, 0), w, f"{pre}.bn3")
            if f"{pre}.downsample.0.weight" in w:
                x = _bn(_conv(x, w[f"{pre}.downsample.0.weight"], s, 0), w,
                        f"{pre}.downsample.1")
            x = relu(y + x)
    x = jnp.mean(x, axis=(1, 2), keepdims=True).reshape(x.shape[0], -1)
    return x @ w["fc.weight"] + w["fc.bias"]
