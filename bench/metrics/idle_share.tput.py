"""% of the traced window with no operation on the device."""
from bench import layers


def read(rec):
    return layers.idle_share(rec)
