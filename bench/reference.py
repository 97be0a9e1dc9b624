"""The plain reference: DAEF written from the paper's Algorithms 1-2.

Straightforward ``jax.numpy`` with no kernels, no batching over tenants and
no engine; it imports nothing of the program.  It follows two of the
program's documented conventions, because they decide which of several
equally valid models comes out, and says so here:

* shared randomness: the per-layer keys are
  ``jax.random.split(jax.random.PRNGKey(seed), len(layer_sizes))``; layer
  ``l``'s auxiliary stage-1 weights are Glorot-uniform and its bias standard
  normal, drawn from ``jax.random.split(keys[l])``;
* the encoder's sign convention (``dsvd.canonicalize_signs``): each column of
  the left singular vectors is flipped so that its largest-magnitude entry is
  positive.

The encoder takes the leading eigenvectors of ``X X^T`` (equal to the left
singular vectors of X), the decoder layers are ROLANN fits of the auxiliary
ELM-AE (targets clipped into the activation's open range by 1e-6, inverted,
and weighted by the squared derivative), with no decoder bias, and the last
layer is a linear ROLANN fit against the input.  Each regularized normal
equation is solved as it stands, ``(G + lambda I) w = m``.

``Precision`` chooses the arithmetic.  The reference proper is float32 with
every matrix product at ``highest`` precision.  The control of the
correctness check (see ``check.py``) is the same code in bfloat16: every
array is stored and every product is formed in bfloat16; element-wise
transforms and the small factorizations are evaluated in float32 from the
bfloat16 values and rounded back, because the inverse activation of a
bfloat16 target next to 1 is infinite and the TPU offers no bfloat16
factorization.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Arch:
    """The configuration as the reference needs it: ``layer_sizes``
    (m0, m1, ..., m0), the ridge terms, logistic hidden layers and a linear
    last layer."""

    layer_sizes: tuple[int, ...]
    lam_hidden: float
    lam_last: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        if cfg.get("act_hidden", "logsig") != "logsig" or \
                cfg.get("act_last", "linear") != "linear":
            raise ValueError("the reference implements logsig hidden layers "
                             "and a linear last layer")
        return cls(tuple(cfg["layer_sizes"]), float(cfg["lam_hidden"]),
                   float(cfg["lam_last"]))


@dataclasses.dataclass(frozen=True)
class Precision:
    dtype: object = jnp.float32

    @property
    def exact(self) -> bool:
        return self.dtype == jnp.float32

    def cast(self, x):
        return jnp.asarray(x).astype(self.dtype)

    def mm(self, a, b):
        if self.exact:
            return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
        return jnp.matmul(self.cast(a), self.cast(b), preferred_element_type=self.dtype)

    def ew(self, fn, *xs):
        """An element-wise transform or factorization, in float32."""
        out = fn(*(jnp.asarray(x, jnp.float32) for x in xs))
        return jax.tree.map(self.cast, out)


FLOAT32 = Precision(jnp.float32)
BFLOAT16 = Precision(jnp.bfloat16)


class Model(NamedTuple):
    weights: tuple    # W1 [m0, m1], W2 [m1, m2], ..., W_L [m_{L-1}, m0]
    biases: tuple     # one per layer after the encoder


def _logsig(z):
    return 1.0 / (1.0 + jnp.exp(-z))


def _targets(d):
    """(dbar, f'(dbar)^2) of logistic targets clipped into (0, 1)."""
    d = jnp.clip(d, EPS, 1.0 - EPS)
    dbar = jnp.log(d) - jnp.log1p(-d)
    s = _logsig(dbar)
    fp = s * (1.0 - s)
    return dbar, fp * fp


def _augment(p: Precision, x):
    return jnp.concatenate([x, jnp.ones((1, x.shape[1]), x.dtype)], axis=0)


def _encoder(p: Precision, g_enc, m1):
    def top(g):
        _, vecs = jnp.linalg.eigh(g)
        u = vecs[:, ::-1][:, :m1]
        idx = jnp.argmax(jnp.abs(u), axis=0)
        sign = jnp.sign(u[idx, jnp.arange(m1)])
        return u * jnp.where(sign == 0, 1.0, sign)[None, :]

    return p.ew(top, g_enc)


def _stage1(key, m_in, m_out):
    k_w, k_b = jax.random.split(key)
    lim = np.sqrt(6.0 / (m_in + m_out))
    w = jax.random.uniform(k_w, (m_in, m_out), jnp.float32, -lim, lim)
    return w, jax.random.normal(k_b, (m_out,), jnp.float32)


def _solve(p: Precision, g, m, lam):
    eye = jnp.eye(g.shape[-1], dtype=jnp.float32)
    return p.ew(lambda a, b: jnp.linalg.solve(a + lam * eye, b[..., None])[..., 0], g, m)


def _hidden_stats(p: Precision, xa, d):
    """Per-output (G_j, m_j) of ROLANN with logistic outputs: G_j =
    Xa diag(f'_j^2) Xa^T and m_j = Xa (f'_j^2 dbar_j), one output at a time."""
    dbar, fsq = p.ew(_targets, d)
    fd = p.cast(p.ew(jnp.multiply, fsq, dbar))

    def one(args):
        fsq_j, fd_j = args
        g = p.mm(xa * fsq_j[None, :], xa.T)
        return g, p.mm(xa, fd_j[:, None])[:, 0]

    return jax.lax.map(one, (fsq, fd))


def _parts(arch: Arch, x, keys, p: Precision):
    """Alg. 1 on one data matrix x [m0, n]: the encoder Gram, every layer's
    statistics and the model solved from them."""
    sizes = arch.layer_sizes
    x = p.cast(x)
    g_enc = p.mm(x, x.T)
    w_enc = _encoder(p, g_enc, sizes[1])
    h = p.ew(_logsig, p.mm(w_enc.T, x))
    weights, biases, stats = [w_enc], [], []
    for li in range(2, len(sizes) - 1):
        w_c1, b_c1 = (p.cast(a) for a in _stage1(keys[li], sizes[li - 1], sizes[li]))
        h_c1 = p.ew(_logsig, p.mm(w_c1.T, h) + b_c1[:, None])
        g, m = _hidden_stats(p, _augment(p, h_c1), h)
        w_aug = _solve(p, g, m, arch.lam_hidden).T          # [m_l + 1, m_{l-1}]
        w_next = w_aug[:-1].T                                # [m_{l-1}, m_l]
        weights.append(w_next)
        biases.append(jnp.zeros((sizes[li],), p.dtype))
        stats.append((g, m))
        h = p.ew(_logsig, p.mm(w_next.T, h))
    ha = _augment(p, h)
    g = p.mm(ha, ha.T)
    m = p.mm(x, ha.T)                                        # [m0, m_{L-1} + 1]
    stats.append((g, m))
    w_aug = p.ew(lambda a, b: jnp.linalg.solve(
        a + arch.lam_last * jnp.eye(a.shape[0], dtype=a.dtype), b.T), g, m)
    weights.append(w_aug[:-1])
    biases.append(w_aug[-1])
    return g_enc, tuple(stats), Model(tuple(weights), tuple(biases))


def layer_keys(seed: int, n_layers: int):
    return jax.random.split(jax.random.PRNGKey(seed), n_layers)


@partial(jax.jit, static_argnames=("arch", "p"))
def _fit(arch: Arch, x, seed, p: Precision) -> Model:
    return _parts(arch, x, layer_keys(seed, len(arch.layer_sizes)), p)[2]


def fit(arch: Arch, x, seed: int, p: Precision = FLOAT32) -> Model:
    """Alg. 1: one model from x [m0, n] with shared-randomness ``seed``."""
    return _fit(arch, x, jnp.int32(seed), p)


@partial(jax.jit, static_argnames=("arch", "p"))
def _federate(arch: Arch, xs, seed, p: Precision) -> Model:
    keys = layer_keys(seed, len(arch.layer_sizes))

    def site(carry, x):
        g_enc, stats, _ = _parts(arch, x, keys, p)
        return jax.tree.map(jnp.add, carry, (g_enc, stats)), None

    g0, s0, _ = jax.eval_shape(lambda x: _parts(arch, x, keys, p), xs[0])
    zero = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), (g0, s0))
    (g_enc, stats), _ = jax.lax.scan(site, zero, xs)
    return _from_stats(arch, g_enc, stats, p)


def _from_stats(arch: Arch, g_enc, stats, p: Precision) -> Model:
    sizes = arch.layer_sizes
    weights, biases = [_encoder(p, g_enc, sizes[1])], []
    for li, (g, m) in zip(range(2, len(sizes) - 1), stats[:-1], strict=True):
        weights.append(_solve(p, g, m, arch.lam_hidden)[:, :-1])
        biases.append(jnp.zeros((sizes[li],), p.dtype))
    g, m = stats[-1]
    w_aug = p.ew(lambda a, b: jnp.linalg.solve(
        a + arch.lam_last * jnp.eye(a.shape[0], dtype=a.dtype), b.T), g, m)
    weights.append(w_aug[:-1])
    biases.append(w_aug[-1])
    return Model(tuple(weights), tuple(biases))


def federate(arch: Arch, xs, seed: int, p: Precision = FLOAT32) -> Model:
    """The paper's federation (section 4.3) of the sites xs [K, m0, n]: each
    site fits locally and reports its encoder Gram and every layer's
    statistics; the sums are solved once.  Sites are visited one at a time."""
    return _federate(arch, xs, jnp.int32(seed), p)


def _forward(weights, biases, x, p: Precision):
    h = p.ew(_logsig, p.mm(p.cast(weights[0]).T, x))
    for w, b in zip(weights[1:-1], biases[:-1], strict=True):
        h = p.ew(_logsig, p.mm(p.cast(w).T, h) + p.cast(b)[:, None])
    return p.mm(p.cast(weights[-1]).T, h) + p.cast(biases[-1])[:, None]


@partial(jax.jit, static_argnames=("p",))
def _scores(weights, biases, x, p: Precision):
    x = p.cast(x)
    recon = _forward(weights, biases, x, p)
    return p.ew(lambda r, t: jnp.mean((r - t) ** 2, axis=0), recon, x)


@partial(jax.jit, static_argnames=("p",))
def _reconstruct(weights, biases, x, p: Precision):
    return _forward(weights, biases, p.cast(x), p)


def scores(model, x, p: Precision = FLOAT32):
    """Per-sample reconstruction MSE [n] of x [m0, n] under ``model`` (any
    object with ``weights`` and ``biases`` in the layout above)."""
    return _scores(tuple(model.weights), tuple(model.biases), x, p)


def reconstruct(model, x, p: Precision = FLOAT32):
    """The model's reconstruction [m0, n] of x [m0, n]."""
    return _reconstruct(tuple(model.weights), tuple(model.biases), x, p)


@partial(jax.jit, static_argnames=("m1",))
def _latent_eig_gap(x, m1):
    vals = jnp.linalg.eigvalsh(jnp.matmul(x, x.T, precision=jax.lax.Precision.HIGHEST))[::-1]
    return (vals[m1 - 1] - vals[m1]) / vals[m1 - 1]


def latent_eig_gap(x, m1: int) -> float:
    """(l_m1 - l_{m1+1}) / l_m1 of the eigenvalues of X X^T in descending
    order: how far apart the encoder's last kept direction and the first
    dropped one are.  Near 0, the encoder is ill-defined."""
    return float(_latent_eig_gap(jnp.asarray(x, jnp.float32), m1))
