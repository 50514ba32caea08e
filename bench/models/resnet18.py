"""Plain ResNet18 (He et al. 2016, CIFAR 3x3 stem) and its fleet data.

The benchmark's own model: the weights it hands the engine and the
reference it checks the engine against.  Nine units (stem + eight
BasicBlocks); BatchNorm uses batch statistics in training and evaluation
alike.  Everything is plain ``jax.numpy``: no kernels, no batching tricks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# class templates are a fixed property of the task, shared by every seed
TEMPLATE_SEED = 20240911


def init(key, cfg, dtype=jnp.float32):
    """(units, head) with He-normal convs; the pytree layout the engine's
    ``resnet18`` model uses.  Widths and strides are the configuration's
    ``stem_channels``, ``stage_channels`` and ``stage_strides``."""
    n_classes, stem = cfg["n_classes"], cfg["stem_channels"]
    stages = list(zip(cfg["stage_channels"], cfg["stage_strides"]))
    ks = list(jax.random.split(key, 2 + 3 * len(stages)))

    def conv(k, kh, cin, cout):
        w = jax.random.normal(k, (kh, kh, cin, cout), jnp.float32)
        return (w * math.sqrt(2.0 / (kh * kh * cin))).astype(dtype)

    def bn(c):
        return {"scale": jnp.ones((c,), dtype), "bias": jnp.zeros((c,), dtype)}

    units = [{"conv": conv(ks[0], 3, cfg["channels"], stem), "bn": bn(stem)}]
    cin, ki = stem, 1
    for cout, stride in stages:
        blk = {"conv1": conv(ks[ki], 3, cin, cout), "bn1": bn(cout),
               "conv2": conv(ks[ki + 1], 3, cout, cout), "bn2": bn(cout)}
        if stride != 1 or cin != cout:
            blk["proj"] = conv(ks[ki + 2], 1, cin, cout)
            blk["bn_proj"] = bn(cout)
        units.append(blk)
        cin, ki = cout, ki + 3
    w = jax.random.normal(ks[-1], (cin, n_classes), jnp.float32)
    head = {"w": (w * math.sqrt(1.0 / cin)).astype(dtype),
            "b": jnp.zeros((n_classes,), dtype)}
    return units, head


def _conv(x, w, stride):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(p, x, eps=1e-5):
    mean = jnp.mean(x, axis=(0, 1, 2), keepdims=True)
    var = jnp.var(x, axis=(0, 1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def apply_unit(p, x, idx, cfg):
    if idx == 0:
        return jax.nn.relu(_bn(p["bn"], _conv(x, p["conv"], 1)))
    stride = cfg["stage_strides"][idx - 1]
    h = jax.nn.relu(_bn(p["bn1"], _conv(x, p["conv1"], stride)))
    h = _bn(p["bn2"], _conv(h, p["conv2"], 1))
    sc = _bn(p["bn_proj"], _conv(x, p["proj"], stride)) if "proj" in p else x
    return jax.nn.relu(h + sc)


def loss(units, head, x, y, cfg):
    """Mean softmax cross-entropy of the whole stack on one batch."""
    h = x
    for i, u in enumerate(units):
        h = apply_unit(u, h, i, cfg)
    logits = jnp.mean(h, axis=(1, 2)) @ head["w"] + head["b"]
    logz = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits.astype(jnp.float32), y[:, None],
                               axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def make_data(seed: int, n_vehicles: int, per_vehicle: int, n_test: int,
              cfg) -> dict:
    """CIFAR-like images (class templates plus noise), every vehicle the
    same number of samples drawn from ``labels_per_vehicle`` of the
    classes: non-IID label skew with equal shard sizes, so no shape depends
    on the seed.  Images are drawn on the device in one program."""
    n_classes, lpv = cfg["n_classes"], cfg["labels_per_vehicle"]
    rng = np.random.default_rng(seed)
    allowed = np.stack([rng.choice(n_classes, size=lpv, replace=False)
                        for _ in range(n_vehicles)])
    pick = rng.integers(0, lpv, size=(n_vehicles, per_vehicle))
    labels = np.take_along_axis(allowed, pick, axis=1).astype(np.int32)
    test_labels = rng.integers(0, n_classes, size=n_test).astype(np.int32)
    key = jax.random.PRNGKey(seed % (2 ** 31))
    hw, ch = cfg["image_size"], cfg["channels"]

    @jax.jit
    def draw(key, labels, test_labels):
        t = jax.random.normal(jax.random.PRNGKey(TEMPLATE_SEED),
                              (n_classes, hw, hw, ch)) * 0.7
        for axis in (1, 2):
            t = (0.25 * jnp.roll(t, 1, axis) + 0.5 * t
                 + 0.25 * jnp.roll(t, -1, axis))
        k1, k2 = jax.random.split(key)
        noise = cfg["noise"]
        x = t[labels] + noise * jax.random.normal(k1, labels.shape
                                                  + (hw, hw, ch))
        xt = t[test_labels] + noise * jax.random.normal(
            k2, test_labels.shape + (hw, hw, ch))
        return x.astype(jnp.float32), xt.astype(jnp.float32)

    x, xt = draw(key, labels, test_labels)
    return {"images": np.asarray(x), "labels": labels,
            "test_images": np.asarray(xt), "test_labels": test_labels}
