"""% of the traced window in which the device sat idle inside a decode step
span but outside its fetch: input transfers and the dispatch of the decode
program (the models layer's host side)."""
from bench import spans


def read(rec):
    return spans.idle_share(rec, "dispatch")
