"""The float32 reference against the program's served tokens at a small
size on the CPU: GQA groups of 2 and of 5 over one KV head (with QKV
bias), on a prompt that misses the prefix cache and on a next turn that
hits it."""
import dataclasses

import numpy as np
import pytest

from bench import spec, weights
from bench import run as R
from bench.references import dense as reference
from bench.tests import small


def serve_two_turns(arch_cfg, seed):
    from repro.serving import EngineConfig, LLMEngine, SamplingParams
    cfg = spec.model_config(arch_cfg)
    eng = LLMEngine(R.make_params(cfg, seed), cfg, EngineConfig(
        batch=2, max_len=256, paged=True, prefix_cache=True,
        kv_cache_dtype="int8"))
    rng = np.random.default_rng(seed)
    first = rng.integers(0, cfg.vocab, 90).astype(np.int32)
    out1 = eng.generate([first], SamplingParams.greedy(max_new_tokens=20))[0]
    hits0 = eng.pool_report()["page_hits"]
    second = np.concatenate([first, np.asarray(out1.token_ids, np.int32),
                             rng.integers(0, cfg.vocab, 30)
                             .astype(np.int32)])
    out2 = eng.generate([second],
                        SamplingParams.greedy(max_new_tokens=20))[0]
    hits = eng.pool_report()["page_hits"] - hits0
    return [(first, np.asarray(out1.token_ids, np.int32)),
            (second, np.asarray(out2.token_ids, np.int32))], hits


@pytest.mark.parametrize("heads,kv_heads,bias", [(4, 2, False), (5, 1, True)])
def test_reference_matches_served_tokens(heads, kv_heads, bias):
    _, _, cfg = small.cell(heads=heads, kv_heads=kv_heads, bias=bias)
    arch = spec.arch_of(cfg)
    seqs, hits = serve_two_turns(cfg, seed=31)
    # the history's 6 full pages are cached; the program adopts hits in
    # whole 4-page chunks, so the second turn hits one chunk
    assert hits == 4
    miss, hit = reference.served_gaps(arch, 31, seqs)
    assert len(miss) == len(hit) == 20
    assert miss.max() < small.LIMIT and hit.max() < small.LIMIT
    # a reference that got RoPE wrong disagrees far beyond the limit
    wrong = dict(arch, rope_theta=arch["rope_theta"] / 1e4)
    bad = reference.served_gaps(wrong, 31, seqs)
    assert max(g.max() for g in bad) > 3 * small.LIMIT


def test_reference_weights_follow_the_initializer():
    """Equal weights without either side handing them over: the
    reference's draw equals the program's initializer's, leaf by leaf."""
    import jax
    from repro.models import transformer
    _, _, cfg = small.cell(heads=5, kv_heads=1, bias=True)
    arch = spec.arch_of(cfg)
    mc = spec.model_config(cfg)
    prog = R.make_params(mc, 2**40 + 9)
    keys = jax.random.split(weights.base_key(2**40 + 9), arch["layers"] + 3)
    root = weights.bias_root(2**40 + 9)
    embed, head = reference._head_weights(keys, arch["d_model"],
                                          transformer.padded_vocab(mc))
    assert np.array_equal(np.asarray(embed), np.asarray(prog["embed"]))
    assert np.array_equal(np.asarray(head), np.asarray(prog["lm_head"]))
    for layer in range(arch["layers"]):
        w = reference._layer_weights(keys[2 + layer], root, layer,
                                     reference.arch_items(arch))
        blk = jax.tree.map(lambda a: a[layer], prog["blocks"]["p0"])
        for name in ("wq", "wk", "wv", "wo", "bq", "bk", "bv"):
            assert np.array_equal(np.asarray(w[name]),
                                  np.asarray(blk["attn"][name])), name
        for name in ("bq", "bk", "bv"):     # drawn, not the zeros of init
            assert float(np.abs(np.asarray(w[name], np.float32)).mean()) \
                > weights.BIAS_STD / 2, name
        for name in ("w_gate", "w_up", "w_down"):
            assert np.array_equal(np.asarray(w[name]),
                                  np.asarray(blk["mlp"][name])), name


def test_vocab_padding_matches():
    from repro.models import transformer
    _, _, cfg = small.cell()
    for v in (512, 19008, 92544):
        mc = dataclasses.replace(spec.model_config(cfg), vocab=v)
        assert reference.padded_vocab(v) == transformer.padded_vocab(mc)
