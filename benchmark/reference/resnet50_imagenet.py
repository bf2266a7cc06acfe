"""Plain reference for ``resnet50_imagenet``: ResNet-50's forward pass and
cross-entropy loss in straightforward float32, written from He et al.
(arXiv:1512.03385, Table 1 and section 3.3/3.4) and Ioffe & Szegedy
(arXiv:1502.03167, algorithm 1). No flax module of the program, no kernel;
``jax.lax.conv_general_dilated`` is the one primitive beyond ``jax.numpy``.
It reads the program's parameter tree by its names and nothing else of it.

Departures from the paper, each because the configuration as run has them:

* v1.5 stride placement (the stride-2 sits on the 3x3, not the first 1x1), as
  torchvision's ``resnet50``;
* NHWC layout; explicit (1,1) padding on 3x3 and (3,3) on the 7x7 (torch
  semantics);
* batch normalisation in TRAINING mode, as in the measured step: statistics
  of the batch itself (biased variance), epsilon 1e-5; the running averages in
  ``mutable`` are not read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-5


def _conv(x, p, stride, pad):
    return jax.lax.conv_general_dilated(
        x, p["kernel"], (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, p):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + EPS) * p["scale"] + p["bias"]


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_bn(_conv(x, p["Conv_0"], 1, 0), p["BatchNorm_0"]))
    y = jax.nn.relu(_bn(_conv(y, p["Conv_1"], stride, 1), p["BatchNorm_1"]))
    y = _bn(_conv(y, p["Conv_2"], 1, 0), p["BatchNorm_2"])
    if "shortcut_conv" in p:
        x = _bn(_conv(x, p["shortcut_conv"], stride, 0), p["shortcut_bn"])
    return jax.nn.relu(x + y)


def logits(params, batch, cfg):
    x = batch["image"].astype(jnp.float32)
    x = jax.nn.relu(_bn(_conv(x, params["stem_conv"], 2, 3), params["stem_bn"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), [(0, 0), (1, 1), (1, 1), (0, 0)])
    block = 0
    for stage, n_blocks in enumerate(cfg["stage_sizes"]):
        for b in range(n_blocks):
            stride = 2 if stage > 0 and b == 0 else 1
            x = _bottleneck(x, params[f"BottleneckBlock_{block}"], stride)
            block += 1
    x = x.mean((1, 2))
    return x @ params["head"]["kernel"] + params["head"]["bias"]


def loss(params, mutable, batch, cfg):
    del mutable  # training-mode batch norm reads no running average
    with jax.default_matmul_precision("highest"):
        lg = logits(jax.tree.map(lambda a: a.astype(jnp.float32), params),
                    batch, cfg)
        logp = jax.nn.log_softmax(lg, axis=-1)
        labels = batch["label"].astype(jnp.int32)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()
