"""The scheduler's own host work per engine step in the whole window, in
ms: delta tick_host_s / delta ticks. tick_host_s sums each step's
duration less its program calls (the program's dispatch.* spans: a launch,
and the time the runtime holds it behind device work enqueued earlier)
and its waits on device results (wait.* spans): admission, hashing,
table pushes, building the programs' inputs, bookkeeping and releases."""
from bench.metrics._window import counter_delta

READS = ("counter:tick_host_s", "counter:ticks")


def read(ctx):
    host = counter_delta(ctx, "tick_host_s", whole=True)
    n = counter_delta(ctx, "ticks", whole=True)
    if host is None or not n:
        return None
    return 1e3 * host / n
