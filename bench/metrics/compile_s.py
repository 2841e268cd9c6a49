"""Seconds of XLA compilation during set-up (weights, warm-up), from jax's
monitoring events; near 0 once every program is in the compile cache."""


def read(rec):
    return rec["compile_s"]
