"""What the metric readers share: the turns and tokens of the window.

A token is delivered when the `step()` that returned it ends; the window
is (t0, t1], from its start to the end of its last step."""
from __future__ import annotations

import numpy as np


def in_window(ctx, t) -> bool:
    return ctx.t0 < t <= ctx.t1


def deliveries(ctx, turn) -> list:
    return [(t, n) for t, n in turn.deliveries if in_window(ctx, t)]


def delivered_tokens(ctx) -> int:
    return sum(n for turn in ctx.turns for _, n in deliveries(ctx, turn))


def first_delivery(turn):
    return turn.deliveries[0][0] if turn.deliveries else None


def p90(values) -> float | None:
    return float(np.percentile(np.asarray(values, np.float64), 90)) \
        if len(values) else None


def decode_lengths(ctx) -> list:
    """For every token the decode scan produced and delivered in the
    window, the row's length at the step that produced it (the step fed
    the token before it, at position len(prompt) + j - 1). A turn's first
    token comes from its prefill, not from decode."""
    out = []
    for turn in ctx.turns:
        j = 0
        S = len(turn.prompt)
        for t, n in turn.deliveries:
            if in_window(ctx, t):
                out.extend(S + k for k in range(max(j, 1), j + n))
            j += n
    return out


def prefills(ctx) -> list:
    """(first uncached position, prompt length) of each turn whose first
    token was delivered in the window."""
    return [(turn.cached, len(turn.prompt)) for turn in ctx.turns
            if turn.deliveries and in_window(ctx, turn.deliveries[0][0])]


def counter_delta(ctx, key: str, whole: bool = False):
    """The change of one engine counter over the traced part of the
    window, or over the `whole` window."""
    edges = ctx.window_counters if whole else ctx.counters
    if edges is None:
        return None
    a, b = edges
    if key not in a or key not in b:
        return None
    return b[key] - a[key]


def program_time(ctx, names) -> float | None:
    """Device seconds of the programs named (module names, exact)."""
    if ctx.trace is None:
        return None
    s = sum(v for k, v in ctx.trace.program_s.items() if k in names)
    return s or None


def op_stats(ctx, needle: str):
    """(device seconds, executions) of the operations whose name holds
    `needle`."""
    if ctx.trace is None:
        return None, 0
    s = sum(v for k, v in ctx.trace.op_s.items() if needle in k)
    n = sum(v for k, v in ctx.trace.op_n.items() if needle in k)
    return (s or None), n
