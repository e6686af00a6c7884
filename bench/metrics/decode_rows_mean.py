"""Rows in a decode step, on average: delta decode_tokens_computed over
the decode steps the chip ran (paged-decode kernel executions in the
trace divided by the layers)."""
from bench.metrics._window import counter_delta, op_stats

READS = ("counter:decode_tokens_computed", "op:/_paged_decode")


def read(ctx):
    toks = counter_delta(ctx, "decode_tokens_computed")
    _, execs = op_stats(ctx, "/_paged_decode")
    if not toks or not execs:
        return None
    return toks / (execs / ctx.arch["layers"])
