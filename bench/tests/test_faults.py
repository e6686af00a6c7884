"""With the timed path broken underneath, the harness reports `correct`
false: once for each fault a serving cell can have on one chip."""
import pytest

from bench.tests import small


@pytest.fixture
def decode_scan(monkeypatch):
    """Replace the program's decode scan by `wrap(original)`."""
    from repro.models import transformer

    def install(wrap):
        monkeypatch.setattr(transformer, "decode_scan",
                            wrap(transformer.decode_scan))
    return install


def test_state_left_unchanged(monkeypatch, decode_scan):
    """The decode scan returns the cache it was given: tokens after the
    first scan attend over a cache that lacks what was decoded."""
    def wrap(orig):
        def scan(params, token, cfg, state, pos, **kw):
            tok, _, toks = orig(params, token, cfg, state, pos, **kw)
            return tok, state, toks
        return scan
    decode_scan(wrap)
    res = small.run(monkeypatch, seed=5)
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > small.LIMIT


def test_token_altered_where_produced(monkeypatch, decode_scan):
    """The scan reports one token per dispatch other than the one it fed
    the model."""
    def wrap(orig):
        def scan(params, token, cfg, state, pos, *, steps, **kw):
            tok, st, toks = orig(params, token, cfg, state, pos,
                                 steps=steps, **kw)
            j = steps // 2
            return tok, st, toks.at[j].set((toks[j] + 1) % cfg.vocab)
        return scan
    decode_scan(wrap)
    res = small.run(monkeypatch, seed=6)
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > small.LIMIT


def test_qkv_bias_dropped(monkeypatch):
    """The program projects q, k and v without their biases, in prefill
    and decode alike; the reference adds them."""
    import dataclasses
    from repro.models import attention
    orig = attention._project_qkv

    def no_bias(p, x, cfg, positions):
        return orig(p, x, dataclasses.replace(cfg, qkv_bias=False), positions)
    monkeypatch.setattr(attention, "_project_qkv", no_bias)
    res = small.run(monkeypatch, heads=5, kv_heads=1, bias=True, seed=7)
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > small.LIMIT
