"""The traffic generator: seeded, in range, same sizes for every seed."""
import numpy as np

from bench import traffic_gen

SESSIONS = traffic_gen.load("sessions")


def sessions(seed, vocab=92544, max_len=6144, clients=1):
    return traffic_gen.make(SESSIONS, vocab=vocab, max_len=max_len,
                            clients=clients, page=256, seed=seed)


def schedule(seed, vocab=92544, max_len=6144, turns=200):
    tr = sessions(seed, vocab, max_len)
    out, h, fresh = [], tr.history(), True
    for _ in range(turns):
        nxt = tr.turn(h)
        if nxt is None:
            h, fresh = tr.history(), True
            continue
        prompt, budget = nxt
        out.append((len(h), fresh, prompt, budget))
        h = np.concatenate([prompt, np.zeros(budget, np.int32)])
        fresh = False
    return out


def test_same_seed_same_schedule():
    a, b = schedule(2**33 + 5), schedule(2**33 + 5)
    assert len(a) == len(b)
    for (ha, fa, pa, ba), (hb, fb, pb, bb) in zip(a, b):
        assert (ha, fa, ba) == (hb, fb, bb)
        assert np.array_equal(pa, pb)
    c = schedule(2**33 + 6)
    assert any(not np.array_equal(x[2], y[2]) for x, y in zip(a, c))


def test_lengths_in_range_and_under_max_len():
    for h_len, fresh, prompt, budget in schedule(7):
        new = len(prompt) - h_len
        assert 64 <= new <= 256
        assert 64 <= budget <= 384
        assert len(prompt) + budget <= 6144
        if fresh:
            assert 3072 <= h_len <= 4608


def test_ids_in_vocab_slice():
    for _, _, prompt, _ in schedule(3, vocab=19008, max_len=8192, turns=50):
        assert prompt.dtype == np.int32
        assert prompt.min() >= 0 and prompt.max() < 19008


def test_every_seed_does_the_same_work():
    """Seeds share every size, in order, and differ in token ids."""
    a, b = schedule(1), schedule(2)
    assert [(h, f, len(p), n) for h, f, p, n in a] == \
        [(h, f, len(p), n) for h, f, p, n in b]
    assert not np.array_equal(a[0][2], b[0][2])


def test_sessions_through_the_generator_interface():
    """What the harness sees: the opening histories, one first turn per
    client that resumes its history, and after a finished turn the same
    client's next turn over that prompt and its output."""
    tr = sessions(2**31 + 9, clients=3)
    opening = tr.opening()
    assert len(opening) == 3
    first = tr.poll(0.0)
    assert len(first) == 3 and tr.poll(0.0) == []
    for h, req in zip(opening, first):
        assert np.array_equal(req.prompt[:len(h)], h)
        assert req.cached == (len(h) // 256) * 256
    out = np.arange(first[0].budget, dtype=np.int32)
    tr.finished(first[0], out)
    (nxt,) = tr.poll(1.0)
    assert nxt.client is first[0].client
    full = np.concatenate([first[0].prompt, out])
    if nxt.cached:                      # the session goes on
        assert np.array_equal(nxt.prompt[:len(full)], full)
        assert nxt.cached == (len(full) // 256) * 256
