"""% of the traced window in which the device sat idle inside a cascade
tier's vote span: digests, the vote program, its fetch and the routing of a
finished request (the cascade router)."""
from bench import spans


def read(rec):
    return spans.idle_share(rec, "vote")
