"""Seeded synthetic federations of one of the paper's anomaly datasets, made
on the device.

The sites of one federation observe one phenomenon: a federation draws one
manifold (its mix, bend and anomaly directions, drawn as ``synth.py`` draws
a replica's), and each site draws its own normals and anomalies from it
around a latent offset of its own, ``mu_site ~ N(0, 0.5^2 I)``: the
feature skew between the sites of a real federation (non-IID data).  The
whole federation is standardized once, by the mean and standard deviation
of its pooled normals, which stands for the preprocessing a federation
agrees on before its first round.  A federation of unrelated manifolds
would have an almost isotropic pooled Gram and an encoder that says little.

Each site keeps the paper's split (``synth.Shape``): its training normals,
then held-out normals and as many anomalies.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import synth

OFFSET_SD = 0.5


def _normal_raw(key, mix, bend, mu, n):
    kz, ke = jax.random.split(key)
    rank, dim = mix.shape[1], mix.shape[0]
    z = mu + jax.random.normal(kz, (rank, n))
    x = mix @ z + 0.6 * jnp.tanh(bend @ (z * z - 1.0))
    return x + 0.08 * jax.random.normal(ke, (dim, n))


def _anomalies_raw(key, mix, bend, ortho, mu, n):
    k1, k2, k3 = jax.random.split(key, 3)
    n_a1 = n // 2
    a1 = 2.2 * jax.random.normal(k1, (mix.shape[0], n_a1))
    base = _normal_raw(k2, mix, bend, mu, n - n_a1)
    push = ortho @ jax.random.normal(k3, (ortho.shape[1], n - n_a1))
    a2 = base + 1.8 * push / jnp.maximum(jnp.linalg.norm(push, axis=0, keepdims=True), 1e-9)
    return jnp.concatenate([a1, a2], axis=1)


def _site(key, mix, bend, ortho, shape: synth.Shape):
    k_mu, k_norm, k_anom = jax.random.split(key, 3)
    mu = OFFSET_SD * jax.random.normal(k_mu, (mix.shape[1], 1))
    x_norm = _normal_raw(k_norm, mix, bend, mu, shape.n_train + shape.n_test_normal)
    x_anom = _anomalies_raw(k_anom, mix, bend, ortho, mu, shape.n_test_anomaly)
    return x_norm, x_anom


@partial(jax.jit, static_argnames=("shape", "sites"))
def _federation(key, shape: synth.Shape, sites: int):
    k_mix, k_bend, k_q, k_sites = jax.random.split(key, 4)
    dim, rank = shape.dim, shape.rank
    mix = jax.random.normal(k_mix, (dim, rank)) / np.sqrt(rank)
    bend = jax.random.normal(k_bend, (dim, rank)) / np.sqrt(rank)
    q, _ = jnp.linalg.qr(jax.random.normal(k_q, (dim, dim)))
    ortho = q[:, rank:]
    x_norm, x_anom = jax.vmap(lambda k: _site(k, mix, bend, ortho, shape))(
        jax.random.split(k_sites, sites))
    mean = x_norm.mean(axis=(0, 2), keepdims=True)
    std = x_norm.std(axis=(0, 2), keepdims=True) + 1e-9
    x_norm = (x_norm - mean) / std
    x_anom = (x_anom - mean) / std
    train = x_norm[:, :, : shape.n_train]
    test = jnp.concatenate([x_norm[:, :, shape.n_train:], x_anom], axis=2)
    return train, test


def federations(seed: int, shape: synth.Shape, sites: int, count: int, place):
    """``count`` federations of ``sites`` sites each from ``seed``, one
    device call a federation.

    Returns two lists of ``count`` device arrays, ``train`` [sites, dim,
    n_train] and ``test`` [sites, dim, n_test] (each site's held-out
    normals, then its anomalies), float32, each passed through ``place``
    (say a ``jax.device_put`` onto the sites' shards)."""
    train, test = [], []
    for key in jax.random.split(synth.key_from_seed(seed), count):
        tr, te = _federation(key, shape, sites)
        train.append(place(tr))
        test.append(place(te))
    return train, test
