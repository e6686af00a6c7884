"""The whole step's share of the chip's bf16 peak: useful FLOPs of the
window (prompts prefilled, as prefill_mfu counts them, and tokens
decoded) over the traced window's length times the peak."""
from bench import flops
from bench.metrics._window import decode_lengths, prefills

READS = ("trace:window",)


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    f = sum(flops.prefill_flops(ctx.arch, lo, hi)
            for lo, hi in prefills(ctx))
    f += sum(flops.decode_flops(ctx.arch, k - 1)
             for k in decode_lengths(ctx))
    if not f:
        return None
    return 100.0 * f / (ctx.trace.window_s * ctx.peaks["bf16_flops_per_s"])
