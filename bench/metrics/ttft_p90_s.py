"""90th percentile, over every turn whose first token was delivered in
the window, of the time from its submission to the end of the step that
returned that token.

A turn still waiting when the window closes is left out: its elapsed
time would be the length of whatever last step the window takes in, and
a few milliseconds at the window's end decide which step that is, and
with it a p90 over some forty turns. A stall inside the window still
shows, in the first tokens delivered after it."""
from bench.metrics._window import first_delivery, in_window, p90

READS = ("host_clock",)


def read(ctx):
    vals = []
    for turn in ctx.turns:
        t = first_delivery(turn)
        if t is not None and in_window(ctx, t):
            vals.append(t - turn.t_submit)
    return p90(vals)
