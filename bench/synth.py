"""Seeded synthetic replicas of the paper's anomaly datasets, made on the device.

The recipe is the one the program's ``data/synthetic.py`` uses for the
paper's Table 1 datasets (normal samples on a random nonlinear manifold of
rank ``dim // 3`` plus noise; anomalies half isotropic far-field noise, half
on-manifold points pushed off it; everything standardized by the normal
class), kept here so that no later change to the program can move the
benchmark's inputs.  It draws with ``jax.random`` instead of NumPy so that
a whole fleet of replicas is one jitted call on the device.

Shapes follow the paper's protocol: fold 0 of 10 over the normal samples is
held out, and the test set adds as many anomalies as it has normals (fewer
where the dataset has fewer anomalies).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from any whole number (wider than 32 bits included)."""
    state = np.random.SeedSequence(int(seed) % 2**64).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(state, jnp.uint32),
                                    impl="threefry2x32")


@dataclasses.dataclass(frozen=True)
class Shape:
    """One replica's sizes: ``dim`` features, ``n_train`` normal training
    samples, ``n_test_normal`` held-out normals and ``n_test_anomaly``
    held-out anomalies."""

    dim: int
    n_train: int
    n_test_normal: int
    n_test_anomaly: int

    @classmethod
    def from_table(cls, dim: int, n_total: int, n_anomaly: int,
                   fold: int = 0, n_folds: int = 10) -> "Shape":
        n_norm = n_total - n_anomaly
        lo, hi = round(fold * n_norm / n_folds), round((fold + 1) * n_norm / n_folds)
        n_test = hi - lo
        return cls(dim, n_norm - n_test, n_test, min(n_anomaly, n_test))

    @property
    def rank(self) -> int:
        return max(2, self.dim // 3)


def _normal_raw(key, mix, bend, n):
    kz, ke = jax.random.split(key)
    rank, dim = mix.shape[1], mix.shape[0]
    z = jax.random.normal(kz, (rank, n))
    x = mix @ z + 0.6 * jnp.tanh(bend @ (z * z - 1.0))
    return x + 0.08 * jax.random.normal(ke, (dim, n))


def _anomalies_raw(key, mix, bend, ortho, n):
    k1, k2, k3 = jax.random.split(key, 3)
    n_a1 = n // 2
    a1 = 2.2 * jax.random.normal(k1, (mix.shape[0], n_a1))
    base = _normal_raw(k2, mix, bend, n - n_a1)
    push = ortho @ jax.random.normal(k3, (ortho.shape[1], n - n_a1))
    a2 = base + 1.8 * push / jnp.maximum(jnp.linalg.norm(push, axis=0, keepdims=True), 1e-9)
    return jnp.concatenate([a1, a2], axis=1)


def _one_replica(key, shape: Shape):
    k_mix, k_bend, k_q, k_norm, k_anom = jax.random.split(key, 5)
    dim, rank = shape.dim, shape.rank
    mix = jax.random.normal(k_mix, (dim, rank)) / np.sqrt(rank)
    bend = jax.random.normal(k_bend, (dim, rank)) / np.sqrt(rank)
    q, _ = jnp.linalg.qr(jax.random.normal(k_q, (dim, dim)))
    ortho = q[:, rank:]
    x_norm = _normal_raw(k_norm, mix, bend, shape.n_train + shape.n_test_normal)
    x_anom = _anomalies_raw(k_anom, mix, bend, ortho, shape.n_test_anomaly)
    mean = x_norm.mean(axis=1, keepdims=True)
    std = x_norm.std(axis=1, keepdims=True) + 1e-9
    x_norm = (x_norm - mean) / std
    x_anom = (x_anom - mean) / std
    train = x_norm[:, : shape.n_train]
    test = jnp.concatenate([x_norm[:, shape.n_train:], x_anom], axis=1)
    return train, test


@partial(jax.jit, static_argnames=("shape", "count"))
def _replicas(key, shape: Shape, count: int):
    return jax.vmap(lambda k: _one_replica(k, shape))(jax.random.split(key, count))


def replicas(seed: int, shape: Shape, count: int):
    """``count`` independent replicas from ``seed``, in one device call.

    Returns host arrays ``train`` [count, dim, n_train] and ``test``
    [count, dim, n_test] (normals first, then anomalies), float32."""
    train, test = _replicas(key_from_seed(seed), shape, count)
    return np.asarray(train), np.asarray(test)

