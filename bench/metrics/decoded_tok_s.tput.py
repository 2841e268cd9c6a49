"""Tokens per second that the tiers' decode steps produced in the window,
one per active slot per step, finished requests or not (the slot
scheduler's throughput).  `answered_tok_s` moves only when an answer
crosses the window's close; this moves with every step."""
from bench import layers


def read(rec):
    return layers.decoded_tok_s(rec)
