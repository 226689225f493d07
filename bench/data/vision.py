"""Synthetic CIFAR-shaped images from the seed, made on the device.

Each class has a fixed prototype: a low-frequency sinusoid pattern and a
colour bias, drawn once from ``proto_seed`` (a property of the task, shared by
every split and seed).  An image is its class's prototype plus Gaussian
noise.  The generator follows the program's own synthetic vision data
(prototypes drawn the same way), but draws labels and noise with
``jax.random`` on the device, so that a 50,000-image training set costs a
fraction of a second.

Labels are balanced: a random permutation of ``arange(N) % classes``.  With
the clients taking consecutive slices of ``samples_per_client`` rows, that is
an IID split of a balanced set.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def prototypes(num_classes: int, size: int, channels: int,
               proto_seed: int) -> np.ndarray:
    rng = np.random.default_rng(proto_seed)
    yy, xx = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    protos = np.zeros((num_classes, size, size, channels), np.float32)
    for c in range(num_classes):
        fx, fy = rng.uniform(0.5, 3.0, 2)
        phase = rng.uniform(0, 2 * np.pi, 2)
        base = (np.sin(2 * np.pi * fx * xx / size + phase[0])
                * np.cos(2 * np.pi * fy * yy / size + phase[1]))
        color = rng.uniform(-0.8, 0.8, channels)
        protos[c] = base[..., None] * 0.6 + color[None, None, :] * 0.4
    return protos


def make(key: jax.Array, cfg: dict) -> tuple[np.ndarray, ...]:
    """(client_x (clients, n, H, W, C) float32, client_y (clients, n) int32,
    eval_x, eval_y) as host arrays, for the configuration ``cfg`` (its image
    shape and classes, and its ``data`` section)."""
    data = cfg["data"]
    protos = jnp.asarray(prototypes(cfg["num_classes"], cfg["image_size"],
                                    cfg["in_channels"], data["proto_seed"]))
    clients, n = data["num_clients"], data["samples_per_client"]

    @jax.jit
    def draw(key):
        def split(key, count):
            kl, kn = jax.random.split(key)
            labels = jax.random.permutation(
                kl, jnp.arange(count, dtype=jnp.int32) % cfg["num_classes"])
            noise = jax.random.normal(kn, (count,) + protos.shape[1:])
            return protos[labels] + data["noise"] * noise, labels

        kt, ke = jax.random.split(key)
        return split(kt, clients * n) + split(ke, data["eval_samples"])

    x, y, ex, ey = (np.asarray(a) for a in draw(key))
    shape = (clients, n) + x.shape[1:]
    return x.reshape(shape), y.reshape(clients, n), ex, ey
