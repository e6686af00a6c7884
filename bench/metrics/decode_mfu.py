"""Useful FLOPs of the decode tokens delivered in the window over the
device time of the decode programs (the scanned decode and the one-token
step) times the chip's bf16 peak."""
from bench import flops
from bench.metrics._window import decode_lengths, program_time

READS = ("program:jit_run", "program:jit_decode_fn")


def read(ctx):
    t = program_time(ctx, ("jit_run", "jit_decode_fn"))
    lengths = decode_lengths(ctx)
    if not t or not lengths or ctx.peaks is None:
        return None
    f = sum(flops.decode_flops(ctx.arch, n - 1) for n in lengths)
    return 100.0 * f / (t * ctx.peaks["bf16_flops_per_s"])
