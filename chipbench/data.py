"""Seeded inputs: corpus rows, query vectors, arrival schedules.

Every array is a pure function of the run's ``--seed``; corpus and query
rows are made on the device in jitted calls. The plain reference
regenerates the corpus from the same functions, so no row the program
has seen is ever handed to it.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

#: tags that split one run seed into independent streams
CORPUS, QUERIES, NOISE, POOL, SKETCH = 1, 2, 3, 4, 5


def key(seed: int, tag: int):
    """PRNG key of one stream of the run seed (any int below 2**63)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), tag)


def rng(seed: int, tag: int) -> np.random.Generator:
    """Host generator of one stream of the run seed."""
    return np.random.default_rng([seed, tag])


def sketch_seed(seed: int) -> int:
    """The projection's seed (the deployment's weights) for this run."""
    return int(rng(seed, SKETCH).integers(2 ** 31 - 1))


@functools.partial(jax.jit, static_argnums=(2, 3))
def unit_rows(k, i, n: int, d: int):
    """Block ``i`` of a stream: n isotropic Gaussian rows scaled to unit
    norm (Deep1B's descriptors are L2-normalized), float32 [n, d]."""
    x = jax.random.normal(jax.random.fold_in(k, i), (n, d), jnp.float32)
    return x / jnp.linalg.norm(x, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnums=(3,))
def near(k, src, noise, d: int):
    """Queries near corpus rows: ``src`` moved by seeded Gaussian noise
    of norm about ``noise`` and renormalized, so each has one true
    near neighbour."""
    eps = jax.random.normal(k, src.shape, jnp.float32)
    q = src + noise * eps / jnp.sqrt(jnp.float32(d))
    return q / jnp.linalg.norm(q, axis=1, keepdims=True)


def corpus_chunk(seed: int, i: int, n: int, d: int):
    """Chunk ``i`` (rows ``i*n .. (i+1)*n``) of the run's corpus."""
    return unit_rows(key(seed, CORPUS), i, n, d)


def pick_sources(seed: int, n_rows: int, m: int) -> np.ndarray:
    """m distinct corpus rows, in request order."""
    r = rng(seed, QUERIES)
    if m <= n_rows // 4:
        out = np.unique(r.integers(0, n_rows, size=2 * m))
        while out.size < m:
            out = np.unique(np.concatenate(
                [out, r.integers(0, n_rows, size=m)]))
        return r.permutation(out)[:m]
    return r.permutation(n_rows)[:m]


def poisson_arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Arrival times (s) of an open Poisson stream at mean ``rate`` over
    ``seconds``: round(rate*seconds) exponential gaps taken at fixed
    quantiles and shuffled by the seed, scaled so the last one falls at
    ``seconds``. Every seed sends the same gaps in another order."""
    m = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(m) + 0.5) / m)
    gaps = rng(seed, QUERIES + 100).permutation(gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())
