"""Useful FLOPs of the prompts prefilled in the window over the device
time of the chunk-prefill programs times the chip's bf16 peak.

Useful: each turn whose first token came in the window, from the first
position its cache does not hold (the history's last full page; 0 for a
new session) to its end, with matmuls, attention over those positions
and the logits once. What the program recomputes beyond that (hits it
adopts only in whole chunks, rows dispatched without work) is not
useful."""
from bench import flops
from bench.metrics._window import prefills, program_time

READS = ("program:jit_chunk_prefill",)


def read(ctx):
    t = program_time(ctx, ("jit_chunk_prefill",))
    spans = prefills(ctx)
    if not t or not spans or ctx.peaks is None:
        return None
    f = sum(flops.prefill_flops(ctx.arch, lo, hi) for lo, hi in spans)
    return 100.0 * f / (t * ctx.peaks["bf16_flops_per_s"])
