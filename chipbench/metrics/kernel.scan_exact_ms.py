"""Device milliseconds of the masked collision top-k per flush."""
from chipbench import readers


def read(layer):
    return readers.per_span(layer, "scan_exact", "bench.flush", "ms")
