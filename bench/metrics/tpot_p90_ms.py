"""90th percentile, over turns with two or more deliveries in the window,
of the time per output token after the first delivery: (last - first
delivery) / (tokens delivered - tokens of the first delivery). A scanned
decode delivers many tokens at once, so the tokens of the first delivery
arrived with it and are not divided into the time after it."""
from bench.metrics._window import deliveries, p90

READS = ("host_clock",)


def read(ctx):
    vals = []
    for turn in ctx.turns:
        d = deliveries(ctx, turn)
        if len(d) < 2:
            continue
        n = sum(k for _, k in d) - d[0][1]
        vals.append((d[-1][0] - d[0][0]) / n * 1e3)
    return p90(vals)
