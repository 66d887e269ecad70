"""Share of the fused project-code-pack kernel's device time its work needs at the chip's peaks."""
from chipbench import readers


def read(layer):
    return readers.roofline(layer, "encode")
