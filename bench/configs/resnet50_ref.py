"""Plain reference of the ``resnet50`` configuration.

ResNet-50 v1.5 (He et al. 2015, arXiv:1512.03385, Table 1; the stride of
a downsampling bottleneck sits on its 3x3 convolution) in straightforward
``jax.numpy``, NCHW input of one image, inference-mode batch norm folded
into a per-channel scale and shift.  It imports nothing of the program
under test.

The weights are made here from the seed in one jitted call, in the layout
the served model takes (HWIO kernels, a dict per bottleneck), and handed
to the program's model registry.  ``arith`` selects the arithmetic
(``bench/precision.py``): ``default``, the precision the configuration
states (bfloat16 convolution operands, float32 accumulation), is what the
served logits are compared with; ``bfloat16`` throughout is the control.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench import precision as P


def _blocks(cfg: dict):
    """(stage, block, c_in, c_mid, stride) of every bottleneck."""
    cin, out = cfg["stem_width"], []
    for s, n in enumerate(cfg["stages"]):
        cmid = cfg["stem_width"] * 2 ** s
        for b in range(n):
            out.append((s, b, cin, cmid, 2 if (b == 0 and s > 0) else 1))
            cin = cmid * cfg["bottleneck_expansion"]
    return out, cin


def _init(cfg: dict, key) -> dict:
    blocks, c_final = _blocks(cfg)
    keys = iter(jax.random.split(key, 4 * len(blocks) + 2))

    def conv(kh, cin, cout):
        std = math.sqrt(2.0 / (kh * kh * cin))
        return jax.random.normal(next(keys), (kh, kh, cin, cout)) * std

    def bn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    w0 = cfg["stem_width"]
    params = {"stem": conv(7, cfg["image"][0], w0), "bn_stem": bn(w0),
              "stages": [[] for _ in cfg["stages"]]}
    e = cfg["bottleneck_expansion"]
    for s, _, cin, cmid, stride in blocks:
        p = {"conv1": conv(1, cin, cmid), "bn1": bn(cmid),
             "conv2": conv(3, cmid, cmid), "bn2": bn(cmid),
             "conv3": conv(1, cmid, cmid * e), "bn3": bn(cmid * e)}
        if stride != 1 or cin != cmid * e:
            p["proj"] = conv(1, cin, cmid * e)
            p["bn_proj"] = bn(cmid * e)
        params["stages"][s].append(p)
    params["fc"] = {"w": jax.random.normal(next(keys), (c_final, cfg["classes"]))
                    * math.sqrt(1.0 / c_final),
                    "b": jnp.zeros((cfg["classes"],))}
    return params


def init_params(cfg: dict, key) -> dict:
    """Weights from the seed, on the device, in one jitted call."""
    return jax.jit(partial(_init, cfg))(key)


def _conv(x, w, stride: int, arith: str):
    """NHWC convolution with SAME padding (TensorFlow's split of odd pads)."""
    return jax.lax.conv_general_dilated(
        P.operand(x, arith), P.operand(w, arith), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                 (1, 2, 2, 1), "SAME")


def forward(cfg: dict, params: dict, images, arith: str = "float32"):
    """images [B, 3, H, W] -> logits [B, classes], computed in ``arith``."""
    dtype = P.dtype(arith)
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    x = images.astype(dtype).transpose(0, 2, 3, 1)

    def bn(y, q):
        return y * q["scale"] + q["bias"]

    x = jax.nn.relu(bn(_conv(x, p["stem"], 2, arith), p["bn_stem"]))
    x = _max_pool_3x3_s2(x)
    blocks, _ = _blocks(cfg)
    for s, b, _, _, stride in blocks:
        q = p["stages"][s][b]
        y = jax.nn.relu(bn(_conv(x, q["conv1"], 1, arith), q["bn1"]))
        y = jax.nn.relu(bn(_conv(y, q["conv2"], stride, arith), q["bn2"]))
        y = bn(_conv(y, q["conv3"], 1, arith), q["bn3"])
        if "proj" in q:
            x = bn(_conv(x, q["proj"], stride, arith), q["bn_proj"])
        x = jax.nn.relu(x + y)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2)).astype(dtype)
    logits = P.operand(x, arith) @ P.operand(p["fc"]["w"], arith) + p["fc"]["b"]
    return logits.astype(jnp.float32)


def make_forward(cfg: dict, arith: str = "float32"):
    """Jitted ``(params, images) -> logits`` at precision HIGHEST."""
    def fn(params, images):
        with jax.default_matmul_precision("highest"):
            return forward(cfg, params, images, arith)
    return jax.jit(fn)
