"""90th percentile, over every turn submitted in the window, of the time
from its submission to the end of the step that returned its first
token. A turn with no token by the window's end counts at its elapsed
time, so a stall shows."""
from bench.metrics._window import first_delivery, p90

READS = ("host_clock",)


def read(ctx):
    vals = []
    for turn in ctx.turns:
        if not ctx.t0 <= turn.t_submit <= ctx.t1:
            continue
        t = first_delivery(turn)
        vals.append((t if t is not None and t <= ctx.t1 else ctx.t1)
                    - turn.t_submit)
    return p90(vals)
