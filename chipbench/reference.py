"""Plain reference for coded random-projection search.

The semantics of the configurations, written out in plain jax.numpy and
numpy with nothing taken from the program under test:

* projection: R [d, k] is standard normal, drawn unit by unit
  (``r_unit`` input rows per unit) from ``fold_in(PRNGKey(seed), u)``;
  a row's projection is ``x @ R`` with both operands rounded to the
  type the configuration states (``precision``: bfloat16) and products
  summed in float32;
* 2-bit coding (paper §4): regions (-inf,-w), [-w,0), [0,w), [w,inf)
  map to codes 0..3;
* packing: 32/b codes per uint32 word, code j in bits b*(j % cpw) of
  word j // cpw;
* exact search: top-k rows by collision count, ties to the lower id;
  rho from the count by inverting the 2-bit collision probability
  (paper Thm 4) on a rho grid;
* scored search: per segment, the top-m rows by count (ties to the lower
  id) are scored by the per-code log-likelihood ratio
  log p_ab(rho_ref) - log p_ab(0) of the bivariate normal cells; the
  top-k by score over all segments is returned, with rho from the
  expected-score curve.

Cell probabilities and calibration curves are computed in float64 on
the host; device work is blocked by corpus chunk so it fits beside
nothing else.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from scipy.special import ndtr

from chipbench import data

ZMAX = 9.0          # N(0,1) mass beyond is below 1e-18
_NODES = np.polynomial.legendre.leggauss(400)


# -- sketch ---------------------------------------------------------------

def projection(seed: int, d: int, k: int, r_unit: int):
    """R [d, k] float32 from the sketch seed, unit by unit."""
    base = jax.random.PRNGKey(seed)
    units = [jax.random.normal(jax.random.fold_in(base, u),
                               (min(r_unit, d - u * r_unit), k), jnp.float32)
             for u in range(-(-d // r_unit))]
    return jnp.concatenate(units)


def code_2bit(z, w: float):
    """Projected values -> int32 codes in 0..3."""
    return ((z >= -w).astype(jnp.int32) + (z >= 0.0).astype(jnp.int32)
            + (z >= w).astype(jnp.int32))


def codes(x, r, w: float, operands: str):
    """Rows [n, d] -> int32 codes [n, k]. The projection rounds x and R
    to ``operands`` (a dtype name) and sums the products in float32:
    "bfloat16" is what the configurations state, "float32" plain
    float32, and a narrower type only a control's."""
    dt = jnp.dtype(operands)
    if dt == jnp.float32:
        z = jnp.dot(x, r, precision=jax.lax.Precision.HIGHEST)
    else:
        # bfloat16 holds every narrower type exactly, and its products
        # exactly in float32
        z = jnp.dot(x.astype(dt).astype(jnp.bfloat16),
                    r.astype(dt).astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
    return code_2bit(z, w)


def pack(c, bits: int) -> np.ndarray:
    """int codes [..., k] -> uint32 words [..., k * bits / 32] (host)."""
    c = np.asarray(c, np.uint32)
    cpw = 32 // bits
    c = c.reshape(c.shape[:-1] + (-1, cpw))
    shifts = np.arange(cpw, dtype=np.uint32) * np.uint32(bits)
    return np.bitwise_or.reduce(c << shifts, axis=-1)


def unpack(words, bits: int, k: int):
    """uint32 words [..., W] -> int32 codes [..., k] (host numpy)."""
    words = np.asarray(words, np.uint32)
    cpw = 32 // bits
    shifts = np.arange(cpw, dtype=np.uint32) * np.uint32(bits)
    c = (words[..., None] >> shifts) & np.uint32((1 << bits) - 1)
    return c.reshape(words.shape[:-1] + (-1,))[..., :k].astype(np.int32)


# -- estimators (float64, host) -------------------------------------------

def _interval(lo: float, hi: float):
    x, wx = _NODES
    return (hi - lo) / 2 * x + (hi + lo) / 2, wx * (hi - lo) / 2


def _phi(z):
    return np.exp(-0.5 * z * z) / math.sqrt(2 * math.pi)


def cell_probs(rho: float, w: float) -> np.ndarray:
    """[4, 4] probabilities of (code(x), code(y)) for a bivariate normal
    pair with correlation rho."""
    rho = min(max(rho, 0.0), 1.0 - 1e-7)
    s = math.sqrt(1.0 - rho * rho)
    bounds = [(-ZMAX, -w), (-w, 0.0), (0.0, w), (w, ZMAX)]
    out = np.zeros((4, 4))
    for a, (lo, hi) in enumerate(bounds):
        z, wz = _interval(lo, hi)
        for b, (c, d) in enumerate(bounds):
            out[a, b] = np.sum(_phi(z) * (ndtr((d - rho * z) / s)
                                          - ndtr((c - rho * z) / s)) * wz)
    return out


def collision_prob(rho: float, w: float) -> float:
    """P(code(x) == code(y)) at correlation rho (paper Thm 4)."""
    rho = min(max(rho, 0.0), 1.0 - 1e-9)
    s = math.sqrt(1.0 - rho * rho)
    z, wz = _interval(0.0, min(w, ZMAX))
    return (1.0 - math.acos(rho) / math.pi
            - 4.0 * float(np.sum(_phi(z) * ndtr((-w + rho * z) / s) * wz)))


@functools.lru_cache(maxsize=4)
def count_curve(w: float, grid: int, rho_max: float):
    """(collision fraction grid, rho grid), increasing, for inversion."""
    rho = np.linspace(0.0, rho_max, grid)
    p = np.maximum.accumulate([collision_prob(r, w) for r in rho])
    return p + 1e-12 * np.arange(grid), rho


def rho_from_counts(counts, k: int, w: float, grid: int, rho_max: float):
    """Collision counts -> rho (count < 0 marks an empty slot: -1)."""
    p, rho = count_curve(w, grid, rho_max)
    c = np.asarray(counts, np.float64)
    return np.where(c < 0, -1.0, np.interp(c / k, p, rho))


@functools.lru_cache(maxsize=4)
def score_tables(w: float, k: int, rho_ref: float, floor: float,
                 grid: int, rho_max: float):
    """(pair scores [4, 4], score grid, rho grid) of the scored search."""
    pair = (np.log(np.maximum(cell_probs(rho_ref, w), floor))
            - np.log(np.maximum(cell_probs(0.0, w), floor)))
    rho = np.linspace(0.0, rho_max, grid)
    g = np.array([k * np.sum(np.maximum(cell_probs(r, w), floor) * pair)
                  for r in rho])
    g = np.maximum.accumulate(g) + 1e-9 * np.arange(grid)
    return pair, g, rho


# -- search ---------------------------------------------------------------

def _onehot(c):
    return [(c == v).astype(jnp.bfloat16) for v in range(4)]


@jax.jit
def _counts(q_codes, c):
    """Collision counts [S, n] of query codes [S, k] against codes [n, k]
    as a sum of one-hot products (0/1 in bf16, exact in f32)."""
    acc = 0.0
    for qv, cv in zip(_onehot(q_codes), _onehot(c)):
        acc = acc + jax.lax.dot_general(
            qv, cv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32)


def _lookup(counts, ids, lo: int):
    """counts [S, n] at global ids [S, t] of rows lo.. (else -2)."""
    n = counts.shape[1]
    local = ids - lo
    inside = (local >= 0) & (local < n)
    got = jnp.take_along_axis(counts, jnp.clip(local, 0, n - 1), axis=1)
    return jnp.where(inside, got, -2)


def search_exact(cfg: dict, seed: int, n_chunks: int, q_codes,
                 prog_ids, operands: str = None):
    """Exact top-k over the first ``n_chunks`` corpus chunks, the corpus
    coded with ``operands`` (default: the configuration's precision).

    Returns (counts [S, top_k], ids [S, top_k], counts of the program's
    ids [S, top_k]; an id outside the corpus reads -2), numpy.
    """
    top_k, chunk, d = cfg["top_k"], cfg["build_chunk_rows"], cfg["d"]
    r = projection(data.sketch_seed(seed), d, cfg["k"], cfg["r_unit"])
    q_codes = jnp.asarray(q_codes)
    prog_ids = jnp.asarray(prog_ids, jnp.int32)
    s = q_codes.shape[0]
    best_v = jnp.full((s, top_k), -1, jnp.int32)
    best_i = jnp.full((s, top_k), -1, jnp.int32)
    prog_v = jnp.full((s, top_k), -2, jnp.int32)
    step = _exact_step(top_k, operands or cfg["precision"], cfg["w"])
    for i in range(n_chunks):
        x = data.corpus_chunk(seed, i, chunk, d)
        best_v, best_i, prog_v = step(x, i * chunk, r, q_codes, prog_ids,
                                      best_v, best_i, prog_v)
    return np.asarray(best_v), np.asarray(best_i), np.asarray(prog_v)


@functools.lru_cache(maxsize=8)
def _exact_step(top_k: int, operands: str, w: float):
    @jax.jit
    def step(x, lo, r, q_codes, prog_ids, best_v, best_i, prog_v):
        cnt = _counts(q_codes, codes(x, r, w, operands))
        v, i = jax.lax.top_k(cnt, top_k)
        # stable top_k over [earlier best | this chunk]: ties keep the
        # earlier, lower ids first
        v, pos = jax.lax.top_k(jnp.concatenate([best_v, v], 1), top_k)
        ids = jnp.take_along_axis(
            jnp.concatenate([best_i, i + lo], 1), pos, axis=1)
        got = _lookup(cnt, prog_ids, lo)
        return v, ids, jnp.where(got > -2, got, prog_v)
    return step


def search_scored(cfg: dict, seed: int, n_chunks: int, q_codes, prog_ids,
                  operands: str = None):
    """Scored top-k, the corpus coded with ``operands`` (default: the
    configuration's precision): per segment the top-m rows by count are
    scored with the pair table; returns (scores [S, top_k], ids [S, top_k],
    scores of the program's ids [S, top_k]; an id outside the corpus
    reads nan), numpy."""
    operands = operands or cfg["precision"]
    top_k, chunk, d, k = (cfg["top_k"], cfg["build_chunk_rows"], cfg["d"],
                          cfg["k"])
    sc = cfg["scoring"]
    m = sc["rerank_m"]
    per_seg = cfg["segment_rows"] // chunk
    pair, _, _ = score_tables(cfg["w"], k, sc["rho_ref"], sc["floor"],
                              sc["grid"], sc["rho_max"])
    r = projection(data.sketch_seed(seed), d, k, cfg["r_unit"])
    q_codes = jnp.asarray(q_codes)
    q_tab = jnp.take(jnp.asarray(pair, jnp.float32), q_codes, axis=0)
    prog_ids = jnp.asarray(prog_ids, jnp.int32)
    s = q_codes.shape[0]
    step = _scored_step(m, operands, cfg["w"])
    seg_s, seg_i = [], []
    prog_s = jnp.full((s, top_k), jnp.nan, jnp.float32)
    for i in range(n_chunks):
        if i % per_seg == 0:
            cv = jnp.full((s, m), -1, jnp.int32)
            ci = jnp.full((s, m), -1, jnp.int32)
            cc = jnp.zeros((s, m, k), jnp.int32)
        x = data.corpus_chunk(seed, i, chunk, d)
        cv, ci, cc, prog_s = step(x, i * chunk, r, q_codes, q_tab, prog_ids,
                                  cv, ci, cc, prog_s)
        if i % per_seg == per_seg - 1 or i == n_chunks - 1:
            sco = _score(q_tab, cc)
            sco = jnp.where(ci >= 0, sco, -jnp.inf)
            v, pos = jax.lax.top_k(sco, top_k)
            seg_s.append(v)
            seg_i.append(jnp.take_along_axis(ci, pos, axis=1))
    v, pos = jax.lax.top_k(jnp.concatenate(seg_s, 1), top_k)
    ids = jnp.take_along_axis(jnp.concatenate(seg_i, 1), pos, axis=1)
    return np.asarray(v), np.asarray(ids), np.asarray(prog_s)


@jax.jit
def _score(q_tab, cand_codes):
    """q_tab [S, k, 4], candidate codes [S, m, k] -> scores [S, m]."""
    t = jnp.take_along_axis(q_tab[:, None], cand_codes[..., None], axis=3)
    return jnp.sum(t[..., 0], axis=-1)


@functools.lru_cache(maxsize=8)
def _scored_step(m: int, operands: str, w: float):
    @jax.jit
    def step(x, lo, r, q_codes, q_tab, prog_ids, cv, ci, cc, prog_s):
        c = codes(x, r, w, operands)
        cnt = _counts(q_codes, c)
        v, i = jax.lax.top_k(cnt, m)
        v, pos = jax.lax.top_k(jnp.concatenate([cv, v], 1), m)
        ids = jnp.take_along_axis(jnp.concatenate([ci, i + lo], 1), pos,
                                  axis=1)
        cand = jnp.concatenate([cc, jnp.take(c, i, axis=0)], 1)
        cand = jnp.take_along_axis(cand, pos[..., None], axis=1)
        n = c.shape[0]
        local = prog_ids - lo
        inside = (local >= 0) & (local < n)
        mine = _score(q_tab, jnp.take(c, jnp.clip(local, 0, n - 1), axis=0))
        return v, ids, cand, jnp.where(inside, mine, prog_s)
    return step


def rho_from_scores(scores, cfg: dict):
    """LUT scores -> rho by the expected-score curve (-inf: empty, -1)."""
    sc = cfg["scoring"]
    _, g, rho = score_tables(cfg["w"], cfg["k"], sc["rho_ref"], sc["floor"],
                             sc["grid"], sc["rho_max"])
    s = np.asarray(scores, np.float64)
    return np.where(np.isfinite(s), np.interp(s, g, rho), -1.0)
