"""Host milliseconds per flush: the flush span minus the device-busy time inside it (the mean over the chips traced)."""
from chipbench import readers


def read(layer):
    return readers.host_ms(layer, "bench.flush")
