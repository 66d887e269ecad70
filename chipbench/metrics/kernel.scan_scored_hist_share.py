"""Share (%) of the fused scored kernel's histogram bins that sweep A visited in the traced flushes (program counters)."""


def read(layer):
    c = layer["counters"]
    if not c.get("hist_bins"):
        return None
    return 100.0 * c.get("hist_bins_visited", 0) / c["hist_bins"]
