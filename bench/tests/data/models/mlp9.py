"""Plain 9-unit split MLP and its fleet data.

Each unit is ``relu(x @ w + b)``; the head is a linear layer under softmax
cross-entropy.  The pytree layout is the one the engine's ``mlp9`` model
uses.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def init(key, cfg, dtype=jnp.float32):
    dim, width, n_units = cfg["dim"], cfg["width"], cfg["n_units"]
    ks = jax.random.split(key, n_units + 1)
    units, d_in = [], dim
    for i in range(n_units):
        w = jax.random.normal(ks[i], (d_in, width)) * math.sqrt(2.0 / d_in)
        units.append({"w": w.astype(dtype), "b": jnp.zeros((width,), dtype)})
        d_in = width
    w = jax.random.normal(ks[-1], (width, cfg["n_classes"]))
    head = {"w": (w * math.sqrt(1.0 / width)).astype(dtype),
            "b": jnp.zeros((cfg["n_classes"],), dtype)}
    return units, head


def apply_unit(p, x, idx, cfg):
    return jax.nn.relu(x @ p["w"] + p["b"])


def loss(units, head, x, y, cfg):
    h = x
    for i, u in enumerate(units):
        h = apply_unit(u, h, i, cfg)
    logits = (h @ head["w"] + head["b"]).astype(jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - gold)


def make_data(seed: int, n_vehicles: int, per_vehicle: int, n_test: int,
              cfg) -> dict:
    """Class-structured feature vectors (class template plus noise), one
    equal shard per vehicle, drawn in bulk."""
    rng = np.random.default_rng(seed)
    dim, n_classes = cfg["dim"], cfg["n_classes"]
    templates = rng.normal(size=(n_classes, dim)).astype(np.float32)
    labels = rng.integers(0, n_classes, size=(n_vehicles, per_vehicle))
    x = templates[labels] + cfg["noise"] * rng.normal(
        size=(n_vehicles, per_vehicle, dim))
    yt = rng.integers(0, n_classes, size=n_test)
    xt = templates[yt] + cfg["noise"] * rng.normal(size=(n_test, dim))
    return {"images": x.astype(np.float32), "labels": labels.astype(np.int32),
            "test_images": xt.astype(np.float32),
            "test_labels": yt.astype(np.int32)}
