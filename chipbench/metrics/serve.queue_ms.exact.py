"""Mean milliseconds a query waited from submit to the start of the flush slice that answered it (program counter)."""


def read(layer):
    c = layer["counters"]
    if "queue_wait_s" not in c or not c.get("queries"):
        return None
    return 1e3 * c["queue_wait_s"] / c["queries"]
