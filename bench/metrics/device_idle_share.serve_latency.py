"""Share of the traced window in which no operation ran on the device."""
from layerreads import idle_share


def read(d):
    return idle_share(d)
