"""KiB a placed engine moved to another chip per search batch in the traced flushes: the query put on the segments' chips and the results gathered to the merge chip (program counters)."""


def read(layer):
    c = layer["counters"]
    if "shard_put_bytes" not in c or not c.get("batches"):
        return None
    return (c["shard_put_bytes"] + c["shard_gather_bytes"]) / 1024 / c["batches"]
