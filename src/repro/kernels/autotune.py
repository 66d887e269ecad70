"""Backend-measured block-size autotuning for the Pallas kernel
families.

The kernels in this package take hand-picked default tile sizes; what
actually wins depends on the backend generation and the workload shape.
This module sweeps each family's *numerics-safe* knobs against real
timed calls and caches the winner, keyed on::

    (backend, op, shape-bucket, dtype)

where the shape bucket rounds every dispatch dimension up to the next
power of two — close shapes share a tuning, far shapes never do, and a
cache entry can never leak across backends.

Numerics invariant (what makes a cache safe to trust blindly): the
sweep space (``SWEEPS``) contains only knobs that provably cannot
change results — query/corpus/candidate row tiles. Streaming order is
fixed by the grid, the running top-k merges are stable, integer
accumulations are order-free, and float scores accumulate per row in a
fixed (word, field) order regardless of tiling. Knobs that ARE part of
an oracle's accumulation-order contract (``packed_linear_bwd``'s
``block_n``, ``encode_fused``/``coded_project``'s ``block_d``) are
pinned to their defaults and never swept. A stale, corrupt, or
wrong-bucket cache entry can therefore only change timing, never
output bits — ``tests/test_autotune.py`` and the block-size-invariance
properties in ``tests/test_kernel_conformance.py`` enforce exactly
this.

Lookup is pure and jit-friendly (a host dict read keyed on static
dims); measurement is explicit and offline: ``tune`` times real calls
(median of ``repeats``, ``block_until_ready``), which only makes sense
on a compiled backend — on CPU, where kernels run in interpret mode,
``tune`` refuses to measure and returns the defaults unless forced or
given an injected ``measure`` function (how the tests drive it
deterministically). ``kernels/ops.py`` consults ``lookup`` on every
dispatch whose caller passed no explicit block sizes, so engines and
services pick up tuned configs transparently; ``serve.ann_service``
can pre-tune its own search shapes at warmup.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Optional

import jax

__all__ = ["SWEEPS", "shape_bucket", "AutotuneCache", "default_cache",
           "set_cache", "lookup", "record_config", "tune",
           "tune_search_ops"]

# op -> {knob: candidate values}. ONLY numerics-safe knobs (row tiles).
# Reduction-axis tiles that fix an accumulation order (packed_linear_bwd
# block_n, encode_fused/coded_project block_d) are deliberately absent.
SWEEPS = {
    "coded_project": {"block_m": (32, 64, 128, 256)},
    "encode_fused": {"block_m": (64, 128, 256)},
    "code_pack": {"block_m": (64, 128, 256, 512)},
    "pack_codes": {"block_m": (64, 128, 256, 512)},
    "collision_counts": {"block_q": (64, 128, 256),
                         "block_n": (64, 128, 256)},
    "packed_collision_counts": {"block_q": (64, 128, 256),
                                "block_n": (64, 128, 256)},
    "packed_topk": {"block_q": (64, 128, 256),
                    "block_n": (256, 512, 1024)},
    "packed_topk_masked": {"block_q": (64, 128, 256),
                           "block_n": (256, 512, 1024)},
    "packed_lut_topk": {"block_q": (32, 64, 128),
                        "block_n": (256, 512, 1024)},
    "packed_lut_topk_masked": {"block_q": (32, 64, 128),
                               "block_n": (256, 512, 1024)},
    "packed_lut_rerank": {"block_q": (32, 64, 128),
                          "block_m": (256, 512, 1024)},
    "fused_scored_topk": {"block_q": (32, 64, 128),
                          "block_n": (256, 512, 1024)},
    "fused_scored_topk_masked": {"block_q": (32, 64, 128),
                                 "block_n": (256, 512, 1024)},
    "packed_linear_fwd": {"block_c": (8, 16, 32),
                          "block_n": (256, 512, 1024)},
    "packed_linear_fwd_masked": {"block_c": (8, 16, 32),
                                 "block_n": (256, 512, 1024)},
    "packed_linear_bwd": {"block_c": (8, 16, 32)},
    "packed_linear_bwd_masked": {"block_c": (8, 16, 32)},
}

_ENV_PATH = "REPRO_AUTOTUNE_CACHE"
_MEASURED_BACKENDS = ("tpu", "gpu")


def _backend() -> str:
    return jax.default_backend()


def _bucket_dim(v: int) -> int:
    """Next power of two >= v (0 stays 0) — the shape-bucket rounding."""
    v = int(v)
    return 0 if v <= 0 else 1 << (v - 1).bit_length()


def shape_bucket(**dims) -> str:
    """Canonical bucket string for a dispatch's static dims: each value
    rounded up to the next power of two, keys sorted — e.g.
    ``n=100000, q=256`` -> ``"n131072-q256"``."""
    return "-".join(f"{k}{_bucket_dim(v)}" for k, v in sorted(dims.items()))


def _key(backend: str, op: str, bucket: str, dtype: str) -> str:
    return f"{backend}|{op}|{bucket}|{dtype}"


class AutotuneCache:
    """(backend, op, shape-bucket, dtype) -> block-size dict, with JSON
    persistence. Entries whose knobs fall outside the op's declared
    sweep space are ignored at read time (stale-schema safety), so a
    cache file can only ever supply knobs the numerics invariant
    covers."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._configs: dict[str, dict] = {}
        if path and os.path.exists(path):
            self.load(path)

    def get(self, backend: str, op: str, bucket: str, dtype: str):
        """The cached config dict, filtered to the op's sweepable knobs;
        None on miss or when nothing valid survives the filter."""
        cfg = self._configs.get(_key(backend, op, bucket, dtype))
        if not cfg:
            return None
        allowed = SWEEPS.get(op, {})
        out = {kn: int(v) for kn, v in cfg.items() if kn in allowed}
        return out or None

    def put(self, backend: str, op: str, bucket: str, dtype: str,
            config: dict):
        """Store one winning config (knobs outside the sweep space are
        rejected loudly — they would break the numerics invariant)."""
        allowed = SWEEPS.get(op, {})
        bad = set(config) - set(allowed)
        if bad:
            raise ValueError(f"non-sweepable knobs for {op}: {sorted(bad)}")
        self._configs[_key(backend, op, bucket, dtype)] = dict(config)

    def save(self, path: Optional[str] = None) -> str:
        """Write the cache as JSON; returns the path written."""
        path = path or self.path
        assert path, "no path bound to this cache"
        tmp = f"{path}.tmp"
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(tmp, "w") as f:
            json.dump({"version": 1, "configs": self._configs}, f,
                      indent=2, sort_keys=True)
        os.replace(tmp, path)
        self.path = path
        return path

    def load(self, path: str) -> "AutotuneCache":
        """Merge entries from a JSON cache file into this cache."""
        with open(path) as f:
            data = json.load(f)
        self._configs.update(data.get("configs", {}))
        self.path = path
        return self

    def clear(self):
        """Drop every entry."""
        self._configs.clear()

    def __len__(self) -> int:
        return len(self._configs)


_CACHE: Optional[AutotuneCache] = None


def default_cache() -> AutotuneCache:
    """The process-global cache; first use loads ``$REPRO_AUTOTUNE_
    CACHE`` if the variable is set and the file exists."""
    global _CACHE
    if _CACHE is None:
        _CACHE = AutotuneCache(os.environ.get(_ENV_PATH) or None)
    return _CACHE


def set_cache(cache: Optional[AutotuneCache]) -> Optional[AutotuneCache]:
    """Swap the process-global cache (None resets to lazy default);
    returns the previous one — how tests isolate themselves."""
    global _CACHE
    prev = _CACHE
    _CACHE = cache
    return prev


def lookup(op: str, dtype, **dims) -> dict:
    """Tuned block sizes for one dispatch, or ``{}`` (use the kernel's
    defaults) on a cold cache / unknown bucket — the call ``ops.py``
    makes when a caller passed no explicit block sizes. Never measures,
    never raises."""
    cache = default_cache()
    return cache.get(_backend(), op, shape_bucket(**dims),
                     str(dtype)) or {}


def record_config(op: str, dtype, dims: dict, config: dict, *,
                  backend: Optional[str] = None,
                  cache: Optional[AutotuneCache] = None):
    """Store ``config`` for (backend, op, bucket(dims), dtype)."""
    cache = cache or default_cache()
    cache.put(backend or _backend(), op, shape_bucket(**dims),
              str(dtype), config)


def _default_measure(run: Callable[[dict], object], config: dict,
                     repeats: int) -> float:
    """Median wall-time of ``run(config)`` with device sync; one warmup
    call first so compile time never biases the vote."""
    jax.block_until_ready(run(config))
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(run(config))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def candidate_configs(op: str) -> list[dict]:
    """The sweep grid for ``op`` as a list of config dicts."""
    knobs = sorted(SWEEPS[op].items())
    grids = [{}]
    for name, values in knobs:
        grids = [dict(g, **{name: v}) for g in grids for v in values]
    return grids


def tune(op: str, run: Callable[[dict], object], dtype, dims: dict, *,
         measure: Optional[Callable] = None, repeats: int = 3,
         cache: Optional[AutotuneCache] = None,
         force: bool = False) -> dict:
    """Sweep ``op``'s block-size grid by timing ``run(config)``, cache
    the winner under (backend, op, bucket(dims), dtype), return it.

    ``run`` executes the op once with the given block kwargs (adapters
    close over real arrays); candidates that raise (a tile the chip's
    compiler refuses at this shape, say) are skipped, and if every
    candidate raises, ``tune`` raises a RuntimeError naming each one and
    its error — a family that cannot compile at all is a fault, not a
    cold cache. On backends where kernels run in interpret mode (CPU)
    timing is meaningless, so without ``force`` or an injected
    ``measure`` this is a no-op returning ``{}`` — safe to call
    unconditionally at service warmup."""
    if measure is None:
        if _backend() not in _MEASURED_BACKENDS and not force:
            return {}
        measure = lambda r, c: _default_measure(r, c, repeats)  # noqa: E731
    best, best_t = None, None
    failures = []
    for config in candidate_configs(op):
        try:
            t = measure(run, config)
        except Exception as e:      # noqa: BLE001 — reported below
            failures.append(f"{config}: {type(e).__name__}: {e}")
            continue
        if best_t is None or t < best_t:
            best, best_t = config, t
    if best is None:
        raise RuntimeError(
            f"autotune {op} at {shape_bucket(**dims)}: every candidate "
            f"failed:\n  " + "\n  ".join(failures))
    record_config(op, dtype, dims, best, cache=cache)
    return best


def tune_search_ops(n: int, w: int, bits: int, k: int, *, q: int = 256,
                    top_k: int = 10, rerank_m: int = 256,
                    table_dtype="float32", seed: int = 0,
                    measure: Optional[Callable] = None,
                    cache: Optional[AutotuneCache] = None,
                    force: bool = False) -> dict:
    """Tune the search-family ops for one corpus shape bucket using
    synthesized representative arrays; returns {op: winning config}.

    The convenience entry point ``serve.ann_service`` warmup calls: a
    no-op (empty dict per op) off-accelerator unless forced, so it is
    always safe to invoke.
    """
    import jax.numpy as jnp

    from repro.kernels import ops as _ops

    if measure is None and _backend() not in _MEASURED_BACKENDS \
            and not force:
        return {}
    kk = jax.random.split(jax.random.PRNGKey(seed), 4)
    q_words = jax.random.bits(kk[0], (q, w), jnp.uint32)
    words_db = jax.random.bits(kk[1], (n, w), jnp.uint32)
    fp = w * (32 // bits) * (1 << bits)
    scales = None
    if str(jnp.dtype(table_dtype)) == "int8":
        # the int8 path takes quantized tables + per-word power-of-two
        # scales (the fused kernel's contract)
        tables = jax.random.randint(kk[2], (q, fp), -127, 128, jnp.int8)
        scales = jnp.full((q, w), 0.0078125, jnp.float32)  # 2**-7
    else:
        tables = jax.random.uniform(kk[2], (q, fp), jnp.float32,
                                    -1.0, 1.0).astype(table_dtype)
    valid = jnp.full(((n + 31) // 32,), 0xFFFFFFFF, jnp.uint32)
    runs = {
        "packed_topk": (
            dict(q=q, n=n, w=w, top_k=top_k), q_words.dtype,
            lambda c: _ops.packed_topk(q_words, words_db, bits, k, top_k,
                                       impl="pallas", **c)),
        "packed_topk_masked": (
            dict(q=q, n=n, w=w, top_k=top_k), q_words.dtype,
            lambda c: _ops.packed_topk_masked(q_words, words_db, valid,
                                              bits, k, top_k,
                                              impl="pallas", **c)),
        "fused_scored_topk": (
            dict(q=q, n=n, w=w, t=fp, top_k=top_k), tables.dtype,
            lambda c: _ops.fused_scored_topk(q_words, tables, words_db,
                                             bits, k, rerank_m, top_k,
                                             scales=scales,
                                             impl="pallas", **c)),
        "fused_scored_topk_masked": (
            dict(q=q, n=n, w=w, t=fp, top_k=top_k), tables.dtype,
            lambda c: _ops.fused_scored_topk_masked(
                q_words, tables, words_db, valid, bits, k, rerank_m,
                top_k, scales=scales, impl="pallas", **c)),
    }
    if scales is None:
        runs["packed_lut_topk"] = (
            dict(q=q, n=n, w=w, t=fp, top_k=top_k), tables.dtype,
            lambda c: _ops.packed_lut_topk(tables, words_db, bits, top_k,
                                           impl="pallas", **c))
    out = {}
    for op, (dims, dtype, run) in runs.items():
        out[op] = tune(op, run, dtype, dims, measure=measure, cache=cache,
                       force=force)
    return out
