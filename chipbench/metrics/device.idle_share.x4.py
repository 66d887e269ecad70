"""Share of the traced stretch in which no operation ran on a device, the mean over the chips traced."""


def read(layer):
    return 100.0 * layer["trace"]["idle_share"]
