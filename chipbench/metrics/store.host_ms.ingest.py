"""Host milliseconds per bulk_load call: the call's span minus the device-busy time inside it."""
from chipbench import readers


def read(layer):
    return readers.host_ms(layer, "bench.bulk_load")
