"""Share of the masked collision top-k's device time its work needs at the chip's peaks."""
from chipbench import readers


def read(layer):
    return readers.roofline(layer, "scan_exact")
