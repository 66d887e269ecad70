"""Device milliseconds of the fused masked scored top-k per flush."""
from chipbench import readers


def read(layer):
    return readers.per_span(layer, "scan_scored", "bench.flush", "ms")
