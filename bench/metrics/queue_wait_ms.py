"""Mean wait of an admission in the whole window, in ms: delta
queue_wait_s / delta admissions, from a request's submission (or its
re-queueing after a preemption) to the tick that gave it a row."""
from bench.metrics._window import counter_delta

READS = ("counter:queue_wait_s", "counter:admissions")


def read(ctx):
    wait = counter_delta(ctx, "queue_wait_s", whole=True)
    n = counter_delta(ctx, "admissions", whole=True)
    if wait is None or not n:
        return None
    return 1e3 * wait / n
