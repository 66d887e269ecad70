"""Padded query rows over rows searched in the traced flushes (program counters)."""


def read(layer):
    c = layer["counters"]
    n = c.get("padded_rows", 0) + c.get("cache_misses", 0)
    return 100.0 * c.get("padded_rows", 0) / n if n else None
