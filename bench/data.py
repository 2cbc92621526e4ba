"""Inputs the benchmark makes from the seed, for traffic files that carry
a ``data`` block. One generator per ``kind``, read from the parameters.

``image_classes``: CIFAR-shaped class images split over clients by
quantity skew (each client holds ``classes_per_client`` portions of
``per_client / classes_per_client`` samples; the portions of each class
are dealt to clients at random from the seed, as the paper's alpha
partition does). Every client holds exactly ``per_client`` samples, so
every seed gives the same batch sizes and the same work. An image is
its class's prototype (a random 4x4 pattern upsampled to the image
size) plus Gaussian noise, with a random contrast and brightness
(the repo's ``data/synthetic.gaussian_images``, made on the device).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _images(key, n, classes, hw, ch, labels, noise):
    kp, kn, ka, kb = jax.random.split(key, 4)
    protos = jax.random.normal(kp, (classes, 4, 4, ch))
    protos = jnp.repeat(jnp.repeat(protos, hw // 4, 1), hw // 4, 2)
    x = protos[labels] + noise * jax.random.normal(kn, (n, hw, hw, ch))
    a = jax.random.uniform(ka, (n, 1, 1, 1), minval=0.8, maxval=1.2)
    b = jax.random.uniform(kb, (n, 1, 1, 1), minval=-0.2, maxval=0.2)
    return x * a + b


def image_classes(p, seed: int):
    K, n, per = p["clients"], p["per_client"], p["classes_per_client"]
    C = p["num_classes"]
    portion = n // per
    if portion * per != n or (K * per) % C:
        raise ValueError(f"image_classes: {K} clients x {per} portions "
                         f"must deal evenly over {C} classes")
    rng = np.random.default_rng(seed)
    portions = np.repeat(np.arange(C), K * per // C)     # one class each
    rng.shuffle(portions)
    labels = np.repeat(portions.reshape(K, per), portion, axis=1)
    x = _images(jax.random.PRNGKey(seed), K * n, C, p["hw"], p["channels"],
                jnp.asarray(labels.reshape(-1)), float(p["noise"]))
    x = np.asarray(x).reshape(K, n, p["hw"], p["hw"], p["channels"])
    return list(x), list(labels.astype(np.int64))


GENERATORS = {"image_classes": image_classes}


def make(params, seed: int):
    """(per-client inputs, per-client labels) for a ``data`` block."""
    return GENERATORS[params["kind"]](params, seed)
