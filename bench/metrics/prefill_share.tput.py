"""% of the served programs' device time spent in chunked prefill (the
slot scheduler's admission work) rather than in decode steps, from the
device trace: a served program that runs the paged decode kernel is a
decode step, the others are prefill chunks."""
from bench import layers


def read(rec):
    return layers.prefill_share(rec)
