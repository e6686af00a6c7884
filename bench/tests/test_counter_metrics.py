"""The per-layer metrics read from the program's tick counters, on
hand-made contexts: each reads its counters' change over the whole
window, and reads nothing (None) where the program has no such counter,
as a program from before the counters existed has not."""
import pytest

from bench import spec
from bench.run import Context

# metric -> (counters at the window's start, at its end, expected value)
CASES = {
    "ttft_scan_share": ({"first_token_scan_s": 1.0, "first_token_s": 2.0},
                        {"first_token_scan_s": 4.0, "first_token_s": 6.0},
                        75.0),
    "queue_wait_ms": ({"queue_wait_s": 0.5, "admissions": 10},
                      {"queue_wait_s": 0.51, "admissions": 14}, 2.5),
    "prefill_dispatch_util": (
        {"prefill_tokens_computed": 100, "prefill_tokens_dispatched": 900},
        {"prefill_tokens_computed": 400, "prefill_tokens_dispatched": 6900},
        5.0),
    "tick_host_ms": ({"tick_host_s": 1.0, "ticks": 100},
                     {"tick_host_s": 1.3, "ticks": 110}, 30.0),
}


def _ctx(a, b):
    # the traced part's counters differ from the whole window's: the
    # readers take the whole window
    return Context(window_counters=(a, b), counters=({}, {}), trace=None)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reads_the_whole_window(name):
    a, b, want = CASES[name]
    assert spec.reader(name).read(_ctx(a, b)) == pytest.approx(want)
    m = next(m for m in spec.benchmark()["per_layer"] if m["name"] == name)
    assert m["source"] == "program_counter"
    assert {f"counter:{k}" for k in a} == set(spec.reader(name).READS)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reads_nothing_without_its_counters(name):
    a, b, _ = CASES[name]
    r = spec.reader(name)
    for key in a:                        # a counter the program lacks
        drop = lambda d: {k: v for k, v in d.items() if k != key}
        assert r.read(_ctx(drop(a), drop(b))) is None
    assert r.read(_ctx(a, a)) is None    # nothing happened in the window
    assert r.read(Context(window_counters=None, counters=None,
                          trace=None)) is None
