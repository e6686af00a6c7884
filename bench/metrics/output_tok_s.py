"""Tokens delivered in the window divided by the window's length."""
from bench.metrics._window import delivered_tokens

READS = ("host_clock",)


def read(ctx):
    return delivered_tokens(ctx) / (ctx.t1 - ctx.t0)
