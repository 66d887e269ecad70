"""The plain reference's scored search (``reference.search_scored``) with
its corpus blocks spread over several chips.

The semantics and the blocks are ``reference.py``'s, unchanged: the
corpus chunks of segment ``s`` are regenerated, coded and counted on
``devices[s % len(devices)]``, each segment keeps its own top-m by count
and scores it there, and the per-segment top-k lists are merged on
``devices[0]`` in segment order. The chips work on their segments at
once, so a corpus of four chips' shares takes about one chip's time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data, reference


def search_scored(cfg: dict, seed: int, n_chunks: int, q_codes, prog_ids,
                  devices):
    """``reference.search_scored`` over ``devices``: (scores [S, top_k],
    ids [S, top_k], scores of the program's ids [S, top_k]; an id outside
    the corpus reads nan), numpy."""
    top_k, chunk, d, k = (cfg["top_k"], cfg["build_chunk_rows"], cfg["d"],
                          cfg["k"])
    sc = cfg["scoring"]
    m = sc["rerank_m"]
    per_seg = cfg["segment_rows"] // chunk
    pair, _, _ = reference.score_tables(cfg["w"], k, sc["rho_ref"],
                                        sc["floor"], sc["grid"],
                                        sc["rho_max"])
    r = reference.projection(data.sketch_seed(seed), d, k, cfg["r_unit"])
    q_codes = jnp.asarray(q_codes)
    q_tab = jnp.take(jnp.asarray(pair, jnp.float32), q_codes, axis=0)
    prog_ids = jnp.asarray(prog_ids, jnp.int32)
    s = q_codes.shape[0]
    step = reference._scored_step(m, cfg["precision"], cfg["w"])
    on = {dev: jax.device_put((r, q_codes, q_tab, prog_ids), dev)
          for dev in devices}
    n_seg = -(-n_chunks // per_seg)
    state = []
    for g in range(n_seg):
        with jax.default_device(devices[g % len(devices)]):
            state.append((jnp.full((s, m), -1, jnp.int32),
                          jnp.full((s, m), -1, jnp.int32),
                          jnp.zeros((s, m, k), jnp.int32),
                          jnp.full((s, top_k), jnp.nan, jnp.float32)))
    # the j-th chunk of every segment, then the next: each round keeps
    # every chip busy, and one wait per round bounds what is queued
    for j in range(per_seg):
        for g in range(min(n_seg, -(-(n_chunks - j) // per_seg))):
            i = g * per_seg + j
            dev = devices[g % len(devices)]
            with jax.default_device(dev):
                x = data.corpus_chunk(seed, i, chunk, d)
            rd, qc, qt, pid = on[dev]
            state[g] = step(x, i * chunk, rd, qc, qt, pid, *state[g])
        jax.block_until_ready(state)
    seg_s, seg_i, prog_s = [], [], np.full((s, top_k), np.nan, np.float32)
    for g, (_, ci, cc, ps) in enumerate(state):
        qt = on[devices[g % len(devices)]][2]
        sco = jnp.where(ci >= 0, reference._score(qt, cc), -jnp.inf)
        v, pos = jax.lax.top_k(sco, top_k)
        seg_s.append(v)
        seg_i.append(jnp.take_along_axis(ci, pos, axis=1))
        ps = np.asarray(ps)
        prog_s = np.where(np.isnan(prog_s), ps, prog_s)
    seg_s, seg_i = jax.device_put((seg_s, seg_i), devices[0])
    v, pos = jax.lax.top_k(jnp.concatenate(seg_s, 1), top_k)
    ids = jnp.take_along_axis(jnp.concatenate(seg_i, 1), pos, axis=1)
    return np.asarray(v), np.asarray(ids), prog_s
