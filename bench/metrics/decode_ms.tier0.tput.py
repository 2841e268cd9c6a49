"""Mean host time of a tier-0 decode step, dispatch to tokens on the host (ms)."""
from bench import layers


def read(rec):
    return layers.decode_ms(rec, 0)
