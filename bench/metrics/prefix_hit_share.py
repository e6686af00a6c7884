"""Share of the prompt pages looked up in the whole window that the prefix
cache held: delta page_hits / (delta page_hits + delta page_misses). The
program adopts hits in whole prefill chunks, so a history page past the
last whole chunk counts as a miss. Read over the whole window, not the
traced part: a few seconds hold too few turns to stand for the mix."""
from bench.metrics._window import counter_delta

READS = ("counter:page_hits", "counter:page_misses")


def read(ctx):
    h = counter_delta(ctx, "page_hits", whole=True)
    m = counter_delta(ctx, "page_misses", whole=True)
    if h is None or m is None or h + m == 0:
        return None
    return 100.0 * h / (h + m)
