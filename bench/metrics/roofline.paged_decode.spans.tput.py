"""`roofline.paged_decode.tput` from the program's decode step spans: each
span's `active` slots and attended `rows` give its paged decode call's
FLOPs and bytes, over the kernel's device time."""
from bench import spans


def read(rec):
    return spans.paged_decode_roofline(rec)
