"""The end-to-end metrics read from the turns' host-clock stamps, on
hand-made windows."""
import numpy as np
import pytest

from bench import spec
from bench.run import Context, Turn


def _turn(uid, t_submit, deliveries, t_done=None):
    return Turn(uid, prompt=[0] * 8, budget=sum(n for _, n in deliveries),
                cached=0, t_submit=t_submit, deliveries=deliveries,
                t_done=t_done)


def _ctx(turns, t0=0.0, t1=10.0):
    return Context(t0=t0, t1=t1, turns=turns)


def test_tpot_counts_turns_finished_in_the_window():
    done = [_turn(i, 1.0, [(2.0, 1), (3.0, 10), (4.0, 10)], t_done=4.0)
            for i in range(4)]                   # 2 s / 20 tokens: 100 ms
    # still decoding at the window's end: its last tick in the window is
    # a long one with a single token, which is no time per output token
    cut = _turn(9, 8.0, [(8.5, 1), (9.0, 4), (10.0, 1)])
    tpot = spec.reader("tpot_p90_ms")
    assert tpot.read(_ctx(done + [cut])) == pytest.approx(100.0)
    # the same turns, the window closed after the cut turn finished: it
    # counts, over its 3.5 s and 9 tokens after the first
    cut.deliveries.append((12.0, 4))
    cut.t_done = 12.0
    want = np.percentile([100.0] * 4 + [3.5 / 9 * 1e3], 90)
    assert tpot.read(_ctx(done + [cut], t1=12.0)) == pytest.approx(want)


def test_tpot_takes_the_window_part_of_a_turn_begun_before_it():
    early = _turn(1, -5.0, [(-4.0, 1), (-1.0, 50), (1.0, 1), (3.0, 20)],
                  t_done=3.0)          # in the window: 2 s over 20 tokens
    assert spec.reader("tpot_p90_ms").read(_ctx([early])) == \
        pytest.approx(100.0)


def test_tpot_reads_nothing_without_a_finished_turn():
    cut = _turn(1, 1.0, [(2.0, 1), (3.0, 10)])
    assert spec.reader("tpot_p90_ms").read(_ctx([cut])) is None


def test_ttft_counts_the_first_tokens_delivered_in_the_window():
    turns = [_turn(i, 1.0, [(1.5, 1)]) for i in range(9)]       # 0.5 s
    turns.append(_turn(9, -2.0, [(4.0, 1)]))     # submitted before t0: 6 s
    turns.append(_turn(10, 9.0, []))             # still waiting at t1
    turns.append(_turn(11, -3.0, [(-1.0, 1)]))   # first token before t0
    assert spec.reader("ttft_p90_s").read(_ctx(turns)) == \
        pytest.approx(0.5 + 0.1 * 5.5)


def test_ttft_shows_a_stall_in_the_window():
    calm = [_turn(i, 1.0, [(1.5, 1)]) for i in range(10)]
    stalled = [_turn(i, 1.0, [(1.5 + (3.0 if i < 2 else 0), 1)])
               for i in range(10)]
    ttft = spec.reader("ttft_p90_s")
    assert ttft.read(_ctx(stalled)) > ttft.read(_ctx(calm)) + 1.0


def test_ttft_reads_nothing_without_a_first_token_in_the_window():
    assert spec.reader("ttft_p90_s").read(_ctx([_turn(1, 9.0, [])])) is None


def test_output_rate_counts_deliveries_in_the_window():
    turns = [_turn(1, -1.0, [(-0.5, 7), (2.0, 10), (10.0, 30), (11.0, 5)])]
    assert spec.reader("output_tok_s").read(_ctx(turns)) == \
        pytest.approx(40 / 10.0)
