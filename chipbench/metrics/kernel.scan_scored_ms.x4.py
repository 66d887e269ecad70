"""Device milliseconds of the fused masked scored top-k per chip per flush: its time on all chips over the flushes, over the chips traced."""
from chipbench import readers


def read(layer):
    ms = readers.per_span(layer, "scan_scored", "bench.flush", "ms")
    return None if ms is None else ms / layer["trace"]["devices"]
