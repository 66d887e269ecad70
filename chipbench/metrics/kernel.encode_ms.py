"""Device milliseconds per ingest chunk of the fused project-code-pack kernel."""
from chipbench import readers


def read(layer):
    return readers.kernel_ms(layer, "encode")
