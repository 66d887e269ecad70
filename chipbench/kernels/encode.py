"""Fused project, code and pack (``kernels/encode_fused.py``
``encode_fused_pallas``): one event per ingest chunk."""

MATCH = r"^%encode_fused_pallas(\.\d+)? = "
PEAK = "bf16_flops"


def work(m: int, d: int, k: int, w: int, **_):
    """(operations, bytes) of one call on m rows of width d.

    Operations: the projection, 2 * m * d * k, against the bf16 peak.
    Bytes: float32 input rows, the float32 projection [d, k] and the
    packed words written back."""
    return 2 * m * d * k, 4 * m * d + 4 * d * k + 4 * m * w
