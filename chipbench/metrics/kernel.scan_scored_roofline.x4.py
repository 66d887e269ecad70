"""Share of the fused masked scored top-k's device time, on all chips, that its work needs at the chip's peaks."""
from chipbench import readers


def read(layer):
    return readers.roofline(layer, "scan_scored")
