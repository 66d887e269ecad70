"""Scan-kernel launches per flush (one per live segment and query chunk)."""
from chipbench import readers


def read(layer):
    return readers.per_span(layer, "scan_exact", "bench.flush", "events")
