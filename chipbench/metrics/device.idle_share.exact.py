"""Share of the traced stretch in which no operation ran on the device."""


def read(layer):
    return 100.0 * layer["trace"]["idle_share"]
