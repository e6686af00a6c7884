"""The QKV biases the harness draws into the program's weights: every
layer with attention gets the draw of its index in the whole stack,
however the program groups its layers."""
import dataclasses
import hashlib

import numpy as np
import pytest

from bench import run as R
from bench import spec
from bench.tests import small


def biases_by_layer(cfg, seed):
    """{layer: (bq, bk, bv)} of the weights `make_params` makes."""
    p = R.make_params(cfg, seed)
    period = len(cfg.block_pattern)
    groups = cfg.n_layers // period
    out = {}
    for i in range(period):
        attn = p["blocks"][f"p{i}"]["attn"]
        for g in range(groups):
            out[g * period + i] = tuple(np.asarray(attn[k][g])
                                        for k in ("bq", "bk", "bv"))
    for j, block in enumerate(p["tail"]):
        out[groups * period + j] = tuple(np.asarray(block["attn"][k])
                                         for k in ("bq", "bk", "bv"))
    return out


@pytest.mark.parametrize("registry,pattern", [
    ("qwen2_5_32b", ("attn", "attn")),        # two groups and a tail layer
    ("qwen2_moe_a2_7b", ("attn", "moe")),     # a dense and an expert group
])
def test_grouped_layers_draw_the_single_group_biases(registry, pattern):
    from repro.configs import get_config
    base = dataclasses.replace(get_config(registry, smoke=True), n_layers=5)
    one = biases_by_layer(dataclasses.replace(base, block_pattern=("attn",)),
                          2**35 + 11)
    grouped = biases_by_layer(
        dataclasses.replace(base, block_pattern=pattern), 2**35 + 11)
    assert sorted(grouped) == sorted(one) == list(range(5))
    for layer in range(5):
        for a, b in zip(one[layer], grouped[layer]):
            assert np.array_equal(a, b), layer
        assert float(np.abs(one[layer][0].astype(np.float32)).mean()) > 0


def test_single_group_draw_unchanged():
    """The biased small cell draws the biases it drew before the harness
    handled several groups (sha256 of bq, bk, bv as served)."""
    _, _, cfg = small.cell(heads=5, kv_heads=1, bias=True)
    attn = R.make_params(spec.model_config(cfg), 2**40 + 9)["blocks"]["p0"][
        "attn"]
    h = hashlib.sha256()
    for name in ("bq", "bk", "bv"):
        h.update(np.asarray(attn[name]).tobytes())
    assert h.hexdigest() == \
        "0d2e34d49d66b91da03be3ddfae4976f16434513c4567fb7042b0fe5954ccfc1"
