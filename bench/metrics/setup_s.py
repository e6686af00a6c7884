"""Set-up: from the process's start to the window's start (loading,
weights, compiling or loading every program, history prefill, warm-up)."""
READS = ("host_clock",)


def read(ctx):
    return ctx.setup_s
