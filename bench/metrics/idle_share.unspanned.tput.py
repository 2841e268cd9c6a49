"""% of the traced window in which the device sat idle with no step span
open on the serving thread: the harness's own loop, and whatever the step
spans miss."""
from bench import spans


def read(rec):
    return spans.idle_share(rec, "unspanned")
