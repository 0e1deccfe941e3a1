"""Inputs of a run, made from the configuration and ``--seed``.

The point set is the configuration's stand-in dataset (the generator of
``repro.data.datasets``: a Gaussian mixture in a low-dimensional latent
space pushed through a random linear map plus noise, at the published N and
dim), made on the device in one jitted call from the configuration's fixed
``data_seed``.  Every run fits the same points, as users refit one dataset,
so every run gets the same neighbor graph and ELL width: the step program
compiles once per checkout, not once per seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n", "dim", "classes", "latent"))
def _mixture(key, *, n: int, dim: int, classes: int, latent: int):
    k_c, k_l, k_z, k_p, k_e = jax.random.split(key, 5)
    centers = 4.0 * jax.random.normal(k_c, (classes, latent), jnp.float32)
    labels = jax.random.randint(k_l, (n,), 0, classes)
    z = centers[labels] + jax.random.normal(k_z, (n, latent), jnp.float32)
    if dim <= latent:
        return z[:, :dim]
    proj = jax.random.normal(k_p, (latent, dim), jnp.float32) / np.sqrt(latent)
    noise = jax.random.normal(k_e, (n, dim), jnp.float32)
    return jnp.dot(z, proj, precision=jax.lax.Precision.HIGHEST) + 0.3 * noise


def points(dataset: dict) -> np.ndarray:
    """The configuration's point set, ``[n, dim]`` float32 on the host."""
    x = _mixture(jax.random.key(int(dataset["data_seed"])),
                 n=int(dataset["n"]), dim=int(dataset["dim"]),
                 classes=int(dataset["classes"]), latent=int(dataset["latent"]))
    return np.asarray(x)
