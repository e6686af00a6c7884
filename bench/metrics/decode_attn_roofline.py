"""The paged-decode kernel's share of its roofline: the bytes it needs
(each decoded row's flushed int8 pages and scale rows at each step, the
query and the output partials; bench/flops.decode_attn_bytes) over the
chip's HBM bandwidth, divided by the kernel's device time. Its FLOPs
are far below the compute roof, so bytes bound it."""
from bench import flops
from bench.metrics._window import decode_lengths, op_stats

READS = ("op:/_paged_decode",)


def read(ctx):
    t, _ = op_stats(ctx, "/_paged_decode")
    lengths = decode_lengths(ctx)
    if not t or not lengths or ctx.peaks is None:
        return None
    need = sum(flops.decode_attn_bytes(ctx.arch, n, ctx.arch["kv_dtype"])
               for n in lengths)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t
