"""% of the chip's bf16 peak that the window's forward FLOPs (every token of
every member of every tier, prefill and decode, attention included) reach
over the window's length."""
from bench import counts as K
from bench import layers


def read(rec):
    fl = layers.window_flops(rec)
    if fl is None:
        return None
    secs = layers.window_seconds(rec)
    return 100.0 * fl / (secs * rec["chips"] * K.peaks(rec["device_kind"])["bf16_flops_per_s"])
