"""DAEF — Deep Autoencoder for Federated learning (paper §4, Algorithms 1-3).

Architecture (Fig. 2): an asymmetric deep autoencoder.

  * encoder: ONE layer whose weights are the truncated left singular vectors
    of the data matrix, obtained by a (distributed) SVD — no bias;
  * decoder: several layers, each trained non-iteratively with the auxiliary
    ELM-AE + ROLANN procedure (elm_ae.train_layer);
  * last layer: ROLANN directly against the original inputs, linear
    activation.

Everything is closed-form — no gradients, no epochs.  The model carries the
mergeable sufficient statistics (encoder factors + per-layer ROLANN
knowledge), so trained models can be aggregated federated-style
(`merge_models`) or updated incrementally (`partial_fit`).

Data convention (paper): X is [features m0, samples n].
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import activations, dsvd, elm_ae, rolann

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DAEFConfig:
    """Hyperparameters (paper Alg. 1 inputs + Appendix Table 5 naming).

    layer_sizes: the paper's ``a`` — [m0, m1, ..., m0]; m1 is the latent
        dimension, the first entry must equal the input dimension and the
        last entry must equal the input dimension (autoencoder).
    """

    layer_sizes: tuple[int, ...]
    lam_hidden: float = 0.01          # lambda_HL
    lam_last: float = 0.1             # lambda_LL
    act_hidden: str = "logsig"        # f_HL
    act_last: str = "linear"          # f_LL
    init: str = "xavier"              # stage-1 initializer (xavier|random|orthogonal)
    aux_bias: str = "zero"            # decoder bias scheme (see elm_ae)
    method: str = "gram"              # "gram" fast path | "svd" paper-faithful
    seed: int = 0                     # shared randomness across federated nodes
    gram_solver: str = "chol"         # gram-knowledge weight solve: "chol"
                                      # (direct Cholesky, the fast default) |
                                      # "eigh" (factorization route) | "auto"
                                      # (chol + eigh rescue for near-singular
                                      # G; under vmapped fleets the rescue
                                      # lowers to a both-branches select)

    def __post_init__(self):
        if len(self.layer_sizes) < 3:
            raise ValueError("DAEF needs at least [m0, m1, m0]")
        if self.layer_sizes[0] != self.layer_sizes[-1]:
            raise ValueError(
                f"autoencoder must reconstruct its input: "
                f"{self.layer_sizes[0]} != {self.layer_sizes[-1]}"
            )
        if self.gram_solver not in rolann.GRAM_SOLVERS:
            raise ValueError(
                f"unknown gram_solver {self.gram_solver!r}: choose from "
                f"{rolann.GRAM_SOLVERS}"
            )

    @property
    def latent_dim(self) -> int:
        return self.layer_sizes[1]

    @property
    def n_decoder_hidden(self) -> int:
        # layers strictly between the latent layer and the output layer
        return len(self.layer_sizes) - 3

    def layer_keys(self) -> list[jax.Array]:
        """Deterministic per-layer keys — the shared randomness every
        federated node derives identically from the agreed seed."""
        return list(layer_keys_from_seed(self.seed, len(self.layer_sizes)))


def layer_keys_from_seed(seed, n_layers: int) -> jax.Array:
    """Stacked per-layer keys [n_layers, 2] from a (possibly traced) seed.

    Kept traceable so a fleet can derive per-tenant randomness from a batched
    seed array under ``vmap`` — identical keys to ``DAEFConfig.layer_keys``.
    """
    root = jax.random.PRNGKey(seed)
    return jax.random.split(root, max(1, n_layers))


class DAEFModel(NamedTuple):
    """Trained model M (Alg. 1 output)."""

    weights: tuple[Array, ...]          # W1 (encoder), W2..WL (decoder)
    biases: tuple[Array, ...]           # decoder biases (len = len(weights)-1)
    encoder_factors: dsvd.SvdFactors    # untruncated U1, S1 (mergeable)
    layer_knowledge: tuple              # ROLANN knowledge per decoder layer
    train_errors: Array                 # per-sample reconstruction MSE on train


@jax.jit
def sample_mse(recon: Array, x: Array) -> Array:
    """Per-sample reconstruction MSE [n] of ``recon`` against ``x`` [m0, n].

    Compiled even when called eagerly: inside a compiled reduction XLA
    fuses the square into the sum (one rounding, as a fused multiply-add),
    which op-by-op dispatch does not, so an eager fit and a compiled one
    would otherwise disagree in the last bit of every train error."""
    return jnp.mean((recon - x) ** 2, axis=0)


def _acts(config: DAEFConfig):
    f_hl = activations.get(config.act_hidden, invertible_required=True)
    f_ll = activations.get(config.act_last, invertible_required=True)
    return f_hl, f_ll


class FitCall(NamedTuple):
    """One compiled fit program and what it is called with: what a fit path
    dispatches and what its ``lower_fit`` lowers.

    ``place`` maps ``args`` to the arguments as the program reads them (the
    input on its device or shards, see `put`); ``finish`` (optional) maps
    the program's output and the placed arguments to what the fit returns.
    """

    fn: Callable
    args: tuple
    kw: dict
    place: Callable[[tuple], tuple]
    finish: Callable | None = None

    def run(self):
        """Place the arguments, then dispatch the program: the host spans
        ``fit.place`` and ``fit.dispatch``."""
        with obs.span("fit.place"):
            args = self.place(self.args)
        with obs.span("fit.dispatch"):
            out = self.fn(*args, **self.kw)
        return out if self.finish is None else self.finish(out, args)

    def lower(self):
        """The lowered program ``run`` would dispatch."""
        return self.fn.lower(*self.place(self.args), **self.kw)


def put(a, sharding=None):
    """An input where its program reads it: ``jax.device_put`` of an array,
    by default onto the device the jit would have used (the default device,
    uncommitted; an array already on a device stays as it is).  A
    ``jax.ShapeDtypeStruct`` takes the sharding instead, so a lowering sees
    what a call would; a value traced by a caller's jit is left to it."""
    if isinstance(a, jax.ShapeDtypeStruct):
        return a if sharding is None else jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                               sharding=sharding)
    if sharding is None and isinstance(a, jax.core.Tracer):
        return a
    return jax.device_put(a, sharding)


def _place_input(args: tuple) -> tuple:
    """Put the input (the argument after the static config) on its device."""
    return (args[0], put(args[1]), *args[2:])


def fit(config: DAEFConfig, x: Array, *, n_partitions: int = 1) -> DAEFModel:
    """Alg. 1 — non-iterative DAEF training on a single host.

    ``n_partitions`` splits the samples to exercise the distributed SVD /
    ROLANN merge paths exactly as the paper describes (the result is
    identical to n_partitions=1 up to numerics).
    """
    with obs.span("fit.prepare"):
        call = _fit_call(config, x, n_partitions=n_partitions)
    return call.run()


def lower_fit(config: DAEFConfig, x, *, chunk_samples: int | None = None):
    """Lower the program that ``fit`` (or ``fit_chunked``, given
    ``chunk_samples``) runs for ``x`` (an array or a
    ``jax.ShapeDtypeStruct``): ``.compile().as_text()`` is what the device
    executes, e.g. which Pallas kernels the fit launches."""
    return _fit_call(config, x, chunk_samples=chunk_samples).lower()


def _fit_call(config: DAEFConfig, x: Array, *, n_partitions: int = 1,
              chunk_samples: int | None = None) -> FitCall:
    """The program ``fit`` / ``fit_chunked`` run, and its arguments.

    The whole fit is one program per (config, input shape): dispatched op
    by op, a TPU would compile every small op of the pipeline on its own."""
    m0 = x.shape[0]
    if m0 != config.layer_sizes[0]:
        raise ValueError(f"input dim {m0} != layer_sizes[0] {config.layer_sizes[0]}")
    # The program reads the seed and the lambdas only through its arguments;
    # keying it on the rest lets per-tenant configs share one compile.
    shared = dataclasses.replace(config, seed=0, lam_hidden=0.0, lam_last=0.0)
    args = (shared, x, config.layer_keys(), config.lam_hidden, config.lam_last)
    if chunk_samples is None:
        return FitCall(_fit_program, args, {"n_partitions": n_partitions}, _place_input)
    if not isinstance(chunk_samples, int) or chunk_samples < 1:
        raise ValueError(f"chunk_samples must be a positive int, got {chunk_samples!r}")
    _require_gram(config, "fit_chunked")
    return FitCall(_fit_chunked_program, args, {"chunk": chunk_samples}, _place_input)


def _fit_core(
    config: DAEFConfig,
    x: Array,
    keys,
    lam_hidden,
    lam_last,
    *,
    n_partitions: int = 1,
) -> DAEFModel:
    """Traceable Alg. 1 body: ``keys`` may be a stacked [L, 2] key array and
    the regularizers traced scalars, so the whole pipeline vmaps over a
    leading tenant axis (core/fleet.py) — everything data-dependent here is
    shape-static."""
    m0, n = x.shape
    f_hl, f_ll = _acts(config)

    # ---- encoder: distributed truncated SVD (lines 5-12) ----
    with jax.named_scope("encoder"):
        parts = _split(x, n_partitions)
        enc = dsvd.dsvd(parts, rank=min(m0, x.shape[1]), method=_dsvd_method(config))
        w_enc = enc.u[:, : config.latent_dim]
        h = f_hl.fn(w_enc.T @ x)  # [m1, n]

    weights = [w_enc]
    biases: list[Array] = []
    knowledge: list = []

    # ---- decoder hidden layers (lines 13-19) ----
    sizes = config.layer_sizes
    for li in range(2, len(sizes) - 1):
        with jax.named_scope(f"layer{li}"):
            with jax.named_scope("forward"):  # the layer's stage-1 key
                key = keys[li]
            res = elm_ae.train_layer(
                key,
                h,
                sizes[li],
                lam_hidden,
                f_hl,
                init=config.init,
                aux_bias=config.aux_bias,
                method=config.method,
                gram_solver=config.gram_solver,
            )
        weights.append(res.w)
        biases.append(res.b)
        knowledge.append(res.knowledge)
        h = res.h

    # ---- last layer: supervised ROLANN to reconstruct X (lines 20-25) ----
    with jax.named_scope(f"layer{len(sizes) - 1}"):
        w_ll, b_ll, k_ll = rolann.fit(
            h, x, f_ll, lam_last, method=config.method,
            gram_solver=config.gram_solver,
        )
    weights.append(w_ll)
    biases.append(b_ll)
    knowledge.append(k_ll)
    with jax.named_scope("errors"):
        recon = f_ll.fn(w_ll.T @ h + b_ll[:, None])
        train_errors = sample_mse(recon, x)

    return DAEFModel(
        weights=tuple(weights),
        biases=tuple(biases),
        encoder_factors=enc,
        layer_knowledge=tuple(knowledge),
        train_errors=train_errors,
    )


_fit_program = jax.jit(_fit_core, static_argnames=("config", "n_partitions"))

# ---------------------------------------------------------------------------
# Streaming / chunked training (bounded-memory Alg. 1)
#
# The paper's sufficient statistics are additive over sample blocks (Eq. 6-9),
# so the whole fit is a FOLD: pass 1 accumulates the encoder Gram chunk by
# chunk, passes 2..L recompute the (cheap) chunk activations on the fly and
# fold each decoder layer's (G, M) via `stats_backend.gram_stats_acc`, and a
# final pass scores the train errors.  Peak memory is O(m^2 + chunk) instead
# of O(m * n); the result is numerically the one-shot gram-method fit (same
# merge algebra, associativity over chunks).
#
# Two drivers share the same per-chunk math:
#   * `fit_chunked`    — x on device, one `lax.scan` per layer (vmappable:
#                        the fleet engine streams whole fleets this way);
#   * `fit_stream`     — x never on device at once: a host chunk source feeds
#                        fixed-shape chunks into one re-traced jitted step per
#                        layer whose accumulators are DONATED, so steady-state
#                        device memory is the running stats plus one chunk.
# ---------------------------------------------------------------------------

def _require_gram(config: DAEFConfig, what: str) -> None:
    if config.method != "gram":
        raise ValueError(
            f"{what} accumulates Gram sufficient statistics chunk by chunk "
            "(method='gram'); method='svd' factors have no additive chunk "
            "form — switch the config to method='gram'"
        )


def _stream_forward(config: DAEFConfig, x: Array, weights, biases) -> Array:
    """Forward one chunk through the encoder + the solved decoder layers so
    far (all hidden activations) — the recompute-on-the-fly of each pass."""
    f_hl, _ = _acts(config)
    with jax.named_scope("forward"):
        h = f_hl.fn(weights[0].T @ x)
        for w, b in zip(weights[1:], biases, strict=True):
            h = f_hl.fn(w.T @ h + b[:, None])
    return h


def _fit_chunked_core(
    config: DAEFConfig,
    x: Array,
    keys,
    lam_hidden,
    lam_last,
    *,
    chunk: int,
) -> DAEFModel:
    """Traceable chunked Alg. 1 body: one `lax.scan` over sample chunks per
    layer, accumulating (G, M) in the scan carry (XLA reuses the carry
    buffers in place; the fused backend's accumulating kernel aliases them
    too).  Vmaps over a leading tenant axis exactly like `_fit_core` — the
    fleet engine's streaming path."""
    m0, n = x.shape
    f_hl, f_ll = _acts(config)
    sizes = config.layer_sizes
    chunk = min(chunk, max(n, 1))
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    with jax.named_scope("encoder"):
        xp = jnp.pad(x, ((0, 0), (0, pad)))
        mask = (jnp.arange(n_chunks * chunk) < n).astype(x.dtype)
        mask = mask.reshape(n_chunks, chunk)
        xc = jnp.moveaxis(xp.reshape(m0, n_chunks, chunk), 1, 0)  # [c#, m0, chunk]

        # ---- pass 1: encoder Gram, chunk by chunk ----
        def enc_step(g, inp):
            xcg, mk = inp
            return g + dsvd.masked_gram(xcg, mk), None

        g_enc, _ = jax.lax.scan(enc_step, jnp.zeros((m0, m0), x.dtype), (xc, mask))
        enc = dsvd.truncate(dsvd.gram_to_factors(g_enc), min(m0, n))
        w_enc = enc.u[:, : config.latent_dim]

    weights = [w_enc]
    biases: list[Array] = []
    knowledge: list = []

    # ---- passes 2..L-1: decoder layers, stats folded per chunk ----
    for li in range(2, len(sizes) - 1):
        with jax.named_scope(f"layer{li}"):
            with jax.named_scope("forward"):
                w_c1, b_c1 = elm_ae.stage1(
                    keys[li], sizes[li - 1], sizes[li], config.init, x.dtype
                )
            solved = (tuple(weights), tuple(biases))

            def layer_step(stats, inp, _solved=solved, _wc1=w_c1, _bc1=b_c1):
                xcg, mk = inp
                h = _stream_forward(config, xcg, *_solved)
                stats = elm_ae.accumulate_layer_stats(
                    stats, _wc1, _bc1, h, f_hl, weights=mk
                )
                return stats, None

            stats0 = rolann.init_stats(sizes[li], sizes[li - 1], f_hl, x.dtype)
            stats, _ = jax.lax.scan(layer_step, stats0, (xc, mask))
            w_next, b_next = elm_ae.layer_from_knowledge(
                stats, keys[li], sizes[li - 1], sizes[li], lam_hidden, f_hl,
                init=config.init, aux_bias=config.aux_bias, dtype=x.dtype,
                gram_solver=config.gram_solver,
            )
        weights.append(w_next)
        biases.append(b_next)
        knowledge.append(stats)

    # ---- pass L: last layer against the original inputs ----
    solved = (tuple(weights), tuple(biases))

    def last_step(stats, inp):
        xcg, mk = inp
        h = _stream_forward(config, xcg, *solved)
        stats = rolann.accumulate_stats(stats, h, xcg, f_ll, weights=mk)
        return stats, None

    with jax.named_scope(f"layer{len(sizes) - 1}"):
        stats0 = rolann.init_stats(sizes[-2], m0, f_ll, x.dtype)
        k_ll, _ = jax.lax.scan(last_step, stats0, (xc, mask))
        w_ll, b_ll = rolann.solve(k_ll, lam_last, gram_solver=config.gram_solver)
    weights.append(w_ll)
    biases.append(b_ll)
    knowledge.append(k_ll)

    # ---- final pass: per-sample train errors ----
    def err_step(carry, inp):
        xcg, _ = inp
        h = _stream_forward(config, xcg, tuple(weights[:-1]), tuple(biases[:-1]))
        recon = f_ll.fn(w_ll.T @ h + b_ll[:, None])
        return carry, sample_mse(recon, xcg)

    with jax.named_scope("errors"):
        _, errs = jax.lax.scan(err_step, jnp.zeros((), x.dtype), (xc, mask))
        train_errors = errs.reshape(-1)[:n]

    return DAEFModel(
        weights=tuple(weights),
        biases=tuple(biases),
        encoder_factors=enc,
        layer_knowledge=tuple(knowledge),
        train_errors=train_errors,
    )


_fit_chunked_program = jax.jit(_fit_chunked_core,
                               static_argnames=("config", "chunk"))

def fit_chunked(config: DAEFConfig, x: Array, *, chunk_samples: int) -> DAEFModel:
    """Alg. 1 with bounded activation memory: `fit`, as a fold over
    ``chunk_samples``-wide sample chunks (see the section comment above).

    Matches ``fit(config, x)`` (gram method) within accumulation-order float
    error for every chunk size, including chunk widths that do not divide n
    (the ragged tail is padded and masked exactly).
    """
    with obs.span("fit.prepare"):
        call = _fit_call(config, x, chunk_samples=chunk_samples)
    return call.run()


# ---- host-streaming driver (data never fully on device) ----

def _stream_chunk_source(batches):
    """Normalize a chunk source into a zero-arg factory of fresh iterators.

    Accepts a zero-arg callable (called once per pass — true streaming, e.g.
    re-opening a file reader), or any iterable (materialized ONCE into a host
    list of chunk references; the chunks themselves are not copied).  The fit
    makes one pass per layer, so one-shot generators are snapshotted.
    """
    if callable(batches):
        return batches
    chunks = list(batches)
    return lambda: iter(chunks)


@functools.lru_cache(maxsize=256)
def _chunk_mask(width: int, n_valid: int) -> jax.Array:
    """One device-resident mask per (width, valid-prefix) — every full chunk
    of a stream reuses a single buffer instead of re-uploading per step."""
    return (jnp.arange(width) < n_valid).astype(jnp.float32)


def _iter_padded_chunks(factory, ndim: int, m0: int, what: str):
    """Yield (chunk, mask, n_valid) with the ragged tail padded to the fixed
    chunk width.  Only the LAST chunk may be narrower; mid-stream width
    changes are an error (the jitted step is traced once per shape)."""
    it = iter(factory())
    prev = next(it, None)
    if prev is None:
        raise ValueError(f"{what}: empty chunk stream")
    width = None
    while prev is not None:
        cur = next(it, None)
        x = prev if isinstance(prev, jax.Array) else np.asarray(prev)
        if x.ndim != ndim or x.shape[-2] != m0:
            raise ValueError(
                f"{what}: chunk shape {getattr(x, 'shape', None)} does not "
                f"match the expected [{'K, ' if ndim == 3 else ''}{m0}, "
                "chunk_samples] layout"
            )
        c = x.shape[-1]
        if width is None:
            width = c
        if c != width:
            if cur is not None or c > width:
                raise ValueError(
                    f"{what}: chunk widths must be fixed ({width}); got a "
                    f"{'mid-stream' if cur is not None else 'wider final'} "
                    f"chunk of width {c} — re-chunk the source (only the "
                    "last chunk may be narrower)"
                )
            pad = [(0, 0)] * (ndim - 1) + [(0, width - c)]
            x = jnp.pad(x, pad) if isinstance(x, jax.Array) else np.pad(x, pad)
        yield x, _chunk_mask(width, c), c
        prev = cur


@partial(jax.jit, donate_argnums=(0,))
def _stream_enc_step(g, x, mask):
    with jax.named_scope("encoder"):
        return g + dsvd.masked_gram(x, mask)


@partial(jax.jit, static_argnames=("config",), donate_argnums=(1,))
def _stream_layer_step(config, stats, params, x, mask):
    weights, biases, w_c1, b_c1 = params
    f_hl, _ = _acts(config)
    h = _stream_forward(config, x, weights, biases)
    return elm_ae.accumulate_layer_stats(stats, w_c1, b_c1, h, f_hl, weights=mask)


@partial(jax.jit, static_argnames=("config",), donate_argnums=(1,))
def _stream_last_step(config, stats, params, x, mask):
    weights, biases = params
    _, f_ll = _acts(config)
    h = _stream_forward(config, x, weights, biases)
    return rolann.accumulate_stats(stats, h, x, f_ll, weights=mask)


def _errors_chunk(config, params, x):
    """Per-sample reconstruction MSE of one chunk under solved weights."""
    weights, biases = params
    _, f_ll = _acts(config)
    with jax.named_scope("errors"):
        h = _stream_forward(config, x, weights[:-1], biases[:-1])
        recon = f_ll.fn(weights[-1].T @ h + biases[-1][:, None])
        return sample_mse(recon, x)


_stream_errors_chunk = partial(jax.jit, static_argnames=("config",))(_errors_chunk)


def fit_stream(config: DAEFConfig, batches) -> DAEFModel:
    """Alg. 1 over data that never fits on device at once.

    ``batches`` is a host chunk source — an iterable of fixed-shape
    ``[m0, chunk_samples]`` arrays (only the last may be narrower), or a
    zero-arg callable returning a fresh iterator per pass (true streaming
    from disk; the fit makes one pass per layer plus an error-scoring pass).
    Each pass feeds chunks into ONE re-traced jitted step whose accumulator
    argument is donated, so steady-state device memory is the running
    O(m^2) statistics plus a single chunk.

    Numerically matches ``fit(config, concatenate(batches))`` (gram method)
    within accumulation-order float error.
    """
    _require_gram(config, "fit_stream")
    factory = _stream_chunk_source(batches)
    keys = config.layer_keys()
    f_hl, f_ll = _acts(config)
    sizes = config.layer_sizes
    m0 = sizes[0]

    # ---- pass 1: encoder Gram ----
    g = None
    n_total = 0
    for x, mask, n_valid in _iter_padded_chunks(factory, 2, m0, "fit_stream"):
        if g is None:
            g = jnp.zeros((m0, m0), jnp.asarray(x).dtype)
        g = _stream_enc_step(g, x, mask)
        n_total += n_valid
    with jax.named_scope("encoder"):
        enc = dsvd.truncate(dsvd.gram_to_factors(g), min(m0, n_total))
        w_enc = enc.u[:, : config.latent_dim]
    dtype = w_enc.dtype

    weights = [w_enc]
    biases: list[Array] = []
    knowledge: list = []

    # ---- passes 2..L-1: decoder layers ----
    for li in range(2, len(sizes) - 1):
        w_c1, b_c1 = elm_ae.stage1(
            keys[li], sizes[li - 1], sizes[li], config.init, dtype
        )
        params = (tuple(weights), tuple(biases), w_c1, b_c1)
        stats = rolann.init_stats(sizes[li], sizes[li - 1], f_hl, dtype)
        for x, mask, _ in _iter_padded_chunks(factory, 2, m0, "fit_stream"):
            stats = _stream_layer_step(config, stats, params, x, mask)
        w_next, b_next = elm_ae.layer_from_knowledge(
            stats, keys[li], sizes[li - 1], sizes[li], config.lam_hidden, f_hl,
            init=config.init, aux_bias=config.aux_bias, dtype=dtype,
            gram_solver=config.gram_solver,
        )
        weights.append(w_next)
        biases.append(b_next)
        knowledge.append(stats)

    # ---- pass L: last layer ----
    params = (tuple(weights), tuple(biases))
    stats = rolann.init_stats(sizes[-2], m0, f_ll, dtype)
    for x, mask, _ in _iter_padded_chunks(factory, 2, m0, "fit_stream"):
        stats = _stream_last_step(config, stats, params, x, mask)
    w_ll, b_ll = rolann.solve(stats, config.lam_last,
                              gram_solver=config.gram_solver)
    weights.append(w_ll)
    biases.append(b_ll)
    knowledge.append(stats)

    # ---- final pass: train errors ----
    params = (tuple(weights), tuple(biases))
    errs = []
    for x, _, n_valid in _iter_padded_chunks(factory, 2, m0, "fit_stream"):
        # collect on host so in-flight device memory stays O(m^2 + chunk);
        # the [n] error pool goes back to device once, as the model leaf.
        # copy=True: np.asarray of a CPU-backend jax.Array is zero-copy and
        # would pin every chunk's device buffer alive.
        errs.append(np.array(_stream_errors_chunk(config, params, x)[:n_valid]))
    train_errors = jnp.asarray(np.concatenate(errs))

    return DAEFModel(
        weights=tuple(weights),
        biases=tuple(biases),
        encoder_factors=enc,
        layer_knowledge=tuple(knowledge),
        train_errors=train_errors,
    )


def predict(config: DAEFConfig, model: DAEFModel, x: Array) -> Array:
    """Alg. 3 — reconstruct test samples x [m0, n]."""
    f_hl, f_ll = _acts(config)
    h = f_hl.fn(model.weights[0].T @ x)  # encoder: no bias
    for w, b in zip(model.weights[1:-1], model.biases[:-1], strict=True):
        h = f_hl.fn(w.T @ h + b[:, None])
    w, b = model.weights[-1], model.biases[-1]
    return f_ll.fn(w.T @ h + b[:, None])


def reconstruction_error(config: DAEFConfig, model: DAEFModel, x: Array) -> Array:
    """Per-sample MSE reconstruction error (the anomaly score)."""
    return sample_mse(predict(config, model, x), x)


# ---------------------------------------------------------------------------
# Federated aggregation / incremental learning
# ---------------------------------------------------------------------------

def merge_models(config: DAEFConfig, a: DAEFModel, b: DAEFModel, x_stats=None) -> DAEFModel:
    """Aggregate two DAEF models trained on different partitions (paper §4.3).

    The exchanged state is exactly what the paper sends through the broker:
    the encoder's (U, S) factors and each decoder layer's (M, U, S) ROLANN
    knowledge.  Weights are re-solved from the merged knowledge.

    NOTE (documented in DESIGN.md): as in the paper, each node computed its
    decoder statistics against its *local* encoder; after the encoders merge
    the decoder statistics are an approximation of the centralized solution.
    For the exact-centralized protocol use `federated.federated_fit`, which
    synchronizes layer-by-layer.
    """
    return _merge_core(
        config, a, b, config.layer_keys(), config.lam_hidden, config.lam_last
    )


def _merge_core(
    config: DAEFConfig,
    a: DAEFModel,
    b: DAEFModel,
    keys,
    lam_hidden,
    lam_last,
) -> DAEFModel:
    """Traceable merge body (see `_fit_core`): vmap-safe over a tenant axis."""
    enc, knowledge, errors = merge_knowledge(config, a, b)
    return _model_from_knowledge(
        config, enc, knowledge, keys, lam_hidden, lam_last, errors
    )


def merge_knowledge(
    config: DAEFConfig, a: DAEFModel, b: DAEFModel
) -> tuple[dsvd.SvdFactors, tuple, Array]:
    """Merge only the exchanged federated state of two models: encoder
    factors (Eq. 2), per-layer ROLANN knowledge (Eq. 8-9 / Gram sums) and the
    train-error pool.  Weight re-solving is separate (`_model_from_knowledge`)
    so a tree reduction pays one solve at the root, not one per merge."""
    merge = rolann.merge_stats if config.method == "gram" else rolann.merge_factors
    enc = dsvd.merge_pair(a.encoder_factors, b.encoder_factors)
    knowledge = tuple(
        merge(ka, kb) for ka, kb in zip(a.layer_knowledge, b.layer_knowledge, strict=True)
    )
    errors = jnp.concatenate([a.train_errors, b.train_errors])
    return enc, knowledge, errors


def _model_from_knowledge(
    config: DAEFConfig,
    enc: dsvd.SvdFactors,
    knowledge,
    keys,
    lam_hidden,
    lam_last,
    train_errors: Array,
) -> DAEFModel:
    """Re-solve every layer's weights from (merged) federated knowledge."""
    f_hl, _ = _acts(config)
    sizes = config.layer_sizes
    w_enc = enc.u[:, : config.latent_dim]
    weights = [w_enc]
    biases: list[Array] = []

    for li in range(2, len(sizes) - 1):
        w, bias = elm_ae.layer_from_knowledge(
            knowledge[li - 2], keys[li], sizes[li - 1], sizes[li], lam_hidden, f_hl,
            init=config.init, aux_bias=config.aux_bias, dtype=w_enc.dtype,
            gram_solver=config.gram_solver,
        )
        weights.append(w)
        biases.append(bias)

    w_ll, b_ll = rolann.solve(knowledge[-1], lam_last,
                              gram_solver=config.gram_solver)
    weights.append(w_ll)
    biases.append(b_ll)

    return DAEFModel(
        weights=tuple(weights),
        biases=tuple(biases),
        encoder_factors=enc,
        layer_knowledge=tuple(knowledge),
        train_errors=train_errors,
    )


def partial_fit(config: DAEFConfig, model: DAEFModel, x_new: Array) -> DAEFModel:
    """Incremental learning: absorb a new data block into a trained model."""
    update = fit(config, x_new)
    return merge_models(config, model, update)


def _split(x: Array, p: int) -> list[Array]:
    if p <= 1:
        return [x]
    n = x.shape[1]
    bounds = [round(i * n / p) for i in range(p + 1)]
    return [x[:, bounds[i] : bounds[i + 1]] for i in range(p)]


def _dsvd_method(config: DAEFConfig) -> str:
    return "gram" if config.method == "gram" else "svd"
