"""Share of time to first token spent in decode scans: over the turns
whose first token the engine delivered in the whole window, delta
first_token_scan_s / delta first_token_s. A turn's first token is sampled
at its prefill boundary but delivered only when that tick's decode scan
returns; first_token_scan_s sums, for each turn, the time from each
scan's launch to the end of its tokens' bookkeeping, in every tick from
submission to delivery. The scan's program call is left out: the
runtime can hold it until the chunk prefill enqueued before it finishes.
Read over the whole window, as prefix_hit_share is: a few seconds hold
too few turns."""
from bench.metrics._window import counter_delta

READS = ("counter:first_token_scan_s", "counter:first_token_s")


def read(ctx):
    scan = counter_delta(ctx, "first_token_scan_s", whole=True)
    total = counter_delta(ctx, "first_token_s", whole=True)
    if scan is None or not total:
        return None
    return 100.0 * scan / total
