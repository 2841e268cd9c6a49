"""% of the traced window in which the device sat idle while the host was
admitting: inside a slot stream's refill, prefill_chunk, prepare or advance
step span (the slot scheduler)."""
from bench import spans


def read(rec):
    return spans.idle_share(rec, "admit")
