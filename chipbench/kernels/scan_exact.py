"""Masked collision top-k (``kernels/packed_collision.py``
``packed_topk_masked_pallas``): one event per live segment per query
chunk of a flush of unscored search."""

MATCH = r"^%packed_topk_masked_pallas(\.\d+)? = "
PEAK = "int8_ops"


def work(q: int, n: int, w: int, k: int, bits: int, top_k: int, **_):
    """(operations, bytes) of one call over n rows for q queries.

    Operations: the collision count written as its one-hot inner
    product, 2 * q * n * k * 2**bits, against the int8 peak. Bytes:
    corpus words, validity bitmask, query words and the (value, row)
    outputs. A kernel that skips rows makes this count stale."""
    ops = 2 * q * n * k * (1 << bits)
    nbytes = 4 * n * w + n // 8 + 4 * q * w + 8 * q * top_k
    return ops, nbytes
