"""90th percentile, over turns finished in the window with two or more
deliveries in it, of the time per output token after the first delivery:
(last - first delivery) / (tokens delivered - tokens of the first
delivery). A scanned decode delivers many tokens at once, so the tokens
of the first delivery arrived with it and are not divided into the time
after it.

A turn still decoding when the window closes has no time per output
token yet: its deliveries in the window may be its last tick or two,
whose time a chunk prefill dominates, and whether that tick falls inside
the window turns on a few milliseconds at its end. Such turns are left
out; a stall at the end still shows in `ttft_p90_s` and `output_tok_s`."""
from bench.metrics._window import deliveries, in_window, p90

READS = ("host_clock",)


def read(ctx):
    vals = []
    for turn in ctx.turns:
        if turn.t_done is None or not in_window(ctx, turn.t_done):
            continue
        d = deliveries(ctx, turn)
        if len(d) < 2:
            continue
        n = sum(k for _, k in d) - d[0][1]
        vals.append((d[-1][0] - d[0][0]) / n * 1e3)
    return p90(vals)
