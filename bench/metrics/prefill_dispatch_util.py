"""Share of the prompt tokens dispatched to chunk prefill in the whole
window that were prompt tokens: delta prefill_tokens_computed / delta
prefill_tokens_dispatched. A dispatch is (batch, width) tokens whatever
number of rows it prefills; the rest is padding the program computes."""
from bench.metrics._window import counter_delta

READS = ("counter:prefill_tokens_computed",
         "counter:prefill_tokens_dispatched")


def read(ctx):
    valid = counter_delta(ctx, "prefill_tokens_computed", whole=True)
    sent = counter_delta(ctx, "prefill_tokens_dispatched", whole=True)
    if valid is None or not sent:
        return None
    return 100.0 * valid / sent
