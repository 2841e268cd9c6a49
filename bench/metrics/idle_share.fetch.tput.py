"""% of the traced window in which the device sat idle inside a fetch step
span: the decode program done, its tokens not yet on the host."""
from bench import spans


def read(rec):
    return spans.idle_share(rec, "fetch")
