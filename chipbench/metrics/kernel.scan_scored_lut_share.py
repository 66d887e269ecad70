"""Share (%) of the fused scored kernel's 8-row chunks that it LUT-scored in the traced flushes (program counters)."""


def read(layer):
    c = layer["counters"]
    if not c.get("lut_chunks"):
        return None
    return 100.0 * c.get("lut_chunks_scored", 0) / c["lut_chunks"]
