"""% of the requests tier 0 decided in the window that it deferred (cascade router)."""
from bench import layers


def read(rec):
    return layers.defer_share(rec, 0)
