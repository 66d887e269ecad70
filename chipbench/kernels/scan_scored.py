"""Fused masked scored top-k (``kernels/fused_scored.py``
``fused_scored_topk_masked_pallas``): one event per live segment per
query chunk of a flush of scored search."""

MATCH = r"^%fused_scored_topk_masked_pallas(\.\d+)? = "
PEAK = "int8_ops"


def work(q: int, n: int, w: int, k: int, bits: int, top_k: int, m: int,
         **_):
    """(operations, bytes) of one call over n rows for q queries.

    Operations: the collision count of every row as its one-hot inner
    product (2 * q * n * k * 2**bits) plus the table lookups that score
    the m candidates kept per query (2 * q * m * k * 2**bits), all
    against the int8 peak. Bytes: corpus words, validity bitmask, query
    words, float32 query tables (q * k * 2**bits) and the outputs."""
    p = 1 << bits
    ops = 2 * q * n * k * p + 2 * q * m * k * p
    nbytes = 4 * n * w + n // 8 + 4 * q * w + 4 * q * k * p + 8 * q * top_k
    return ops, nbytes
