"""% of the paged decode kernel's device time that its calls need at the chip's peaks."""
from bench import layers


def read(rec):
    return layers.paged_decode_roofline(rec)
