"""The key table: what a configuration file hands to the yardstick
(`arch_of`) and to the program (`model_config`), and what it is refused.

The two cells' configurations are pinned to the values the harness gave
them before the table could state experts or windows; two small files in
`data/` use the key names of Qwen2-MoE's and Mellum 2's config.json."""
import copy
import dataclasses
import json
import pathlib

import pytest

from bench import spec

DATA = pathlib.Path(__file__).resolve().parent / "data"

DENSE = {"experts_total": 0, "experts_held": 0, "top_k": 0, "moe_d_ff": 0,
         "shared_d_ff": 0, "shared_gate": False, "norm_topk": False,
         "window": None}

ARCH = {
    "internlm2_1_8b": {
        "layers": 24, "d_model": 2048, "heads": 16, "kv_heads": 8,
        "head_dim": 128, "d_ff": 8192, "vocab": 92544, "qkv_bias": False,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06, "page": 256,
        "kv_dtype": "int8", **DENSE, "dense_layers": 24,
        "layer_kinds": (("full", "dense"),) * 24},
    "qwen2_5_32b_pp8": {
        "layers": 8, "d_model": 5120, "heads": 40, "kv_heads": 8,
        "head_dim": 128, "d_ff": 27648, "vocab": 19008, "qkv_bias": True,
        "rope_theta": 1000000.0, "rms_norm_eps": 1e-06, "page": 256,
        "kv_dtype": "int8", **DENSE, "dense_layers": 8,
        "layer_kinds": (("full", "dense"),) * 8},
}

# every field the harness set before the table, and the fields it sets
# now, as the harness gave them to the program then
FIELDS = {
    "internlm2_1_8b": dict(
        n_layers=24, d_model=2048, n_heads=16, n_kv_heads=8, head_dim=128,
        d_ff=8192, vocab=92544, qkv_bias=False, rope_theta=1000000.0,
        tie_embeddings=False, dtype="bfloat16", n_experts=0,
        n_shared_experts=0, top_k=0, moe_d_ff=None, sliding_window=None,
        block_pattern=("attn",)),
    "qwen2_5_32b_pp8": dict(
        n_layers=8, d_model=5120, n_heads=40, n_kv_heads=8, head_dim=128,
        d_ff=27648, vocab=19008, qkv_bias=True, rope_theta=1000000.0,
        tie_embeddings=False, dtype="bfloat16", n_experts=0,
        n_shared_experts=0, top_k=0, moe_d_ff=None, sliding_window=None,
        block_pattern=("attn",)),
}


def data(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def program_has(field: str) -> bool:
    from repro.configs.base import ModelConfig
    return field in {f.name for f in dataclasses.fields(ModelConfig)}


@pytest.mark.parametrize("name", sorted(ARCH))
def test_arch_of_cells_unchanged(name):
    assert spec.arch_of(spec.config(name)) == ARCH[name]


@pytest.mark.parametrize("name", sorted(FIELDS))
def test_model_config_cells_unchanged(name):
    from repro.configs import get_config
    from repro.core.quantization import QuantConfig
    cfg = spec.config(name)
    want = dataclasses.replace(
        get_config(cfg["registry"]), **FIELDS[name],
        quant=QuantConfig(granularity="per_block", block_size=256))
    assert spec.model_config(cfg) == want


def test_every_key_of_the_cells_is_known():
    for cell in spec.benchmark()["workloads"]:
        cfg = spec.config(cell["config"])
        assert set(cfg) <= spec.KNOWN


@pytest.mark.parametrize("name", ["qwen2_moe_small", "window_small"])
def test_program_fields_are_the_tables(name):
    """model_config sets every field the key table names, and no other."""
    table = {f for _, _, f in spec.KEY_TABLE if f}
    assert {row[0] for row in spec.program_fields(data(name))} == table


def test_qwen2_moe_file_reaches_the_program():
    cfg = data("qwen2_moe_small")
    a = spec.arch_of(cfg)
    assert (a["experts_total"], a["experts_held"], a["top_k"],
            a["moe_d_ff"], a["shared_d_ff"]) == (8, 8, 2, 32, 64)
    assert a["shared_gate"] and a["norm_topk"] and a["qkv_bias"]
    # use_sliding_window false: the stated window is not used
    assert a["window"] is None and a["dense_layers"] == 0
    assert a["layer_kinds"] == (("full", "moe"),) * 4
    mc = spec.model_config(cfg)
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads, mc.head_dim,
            mc.d_ff, mc.vocab, mc.qkv_bias) == (4, 64, 4, 4, 16, 128, 256,
                                                True)
    # the shared width 64 is two experts of moe_intermediate_size 32
    assert (mc.n_experts, mc.top_k, mc.moe_d_ff, mc.n_shared_experts) == \
        (8, 2, 32, 2)
    assert mc.block_pattern == ("moe",) and mc.sliding_window is None
    assert mc.quant.block_size == 16


def test_window_file_reaches_the_program():
    cfg = data("window_small")
    a = spec.arch_of(cfg)
    assert a["window"] == 48 and a["rope_theta"] == 500000.0
    assert a["layer_kinds"] == (("sliding", "dense"),) * 4
    mc = spec.model_config(cfg)
    assert (mc.sliding_window, mc.block_pattern, mc.n_kv_heads,
            mc.rope_theta) == (48, ("attn",), 2, 500000.0)


@pytest.mark.parametrize("keys,kinds,pattern", [
    ({"first_k_dense_replace": 1}, ["dense"] + ["moe"] * 3,
     ("attn", "moe", "moe", "moe")),
    ({"decoder_sparse_step": 2}, ["dense", "moe"] * 2, ("attn", "moe")),
    ({"mlp_only_layers": [0, 2]}, ["dense", "moe"] * 2, ("attn", "moe")),
    ({"mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
      "decoder_sparse_step": None},
     ["dense"] + ["moe"] * 3, ("attn", "moe", "moe", "moe")),
])
def test_expert_layers_map_to_the_block_pattern(keys, kinds, pattern):
    """Each key that places the experts; None leaves a key out."""
    cfg = {k: v for k, v in dict(data("qwen2_moe_small"), **keys).items()
           if v is not None}
    a = spec.arch_of(cfg)
    assert [f for _, f in a["layer_kinds"]] == kinds
    assert a["dense_layers"] == kinds.index("moe")
    assert spec.model_config(cfg).block_pattern == pattern


def test_max_window_layers_sets_the_sliding_layers():
    cfg = dict(data("qwen2_moe_small"), use_sliding_window=True,
               sliding_window=64, max_window_layers=2)
    a = spec.arch_of(cfg)
    assert [k for k, _ in a["layer_kinds"]] == ["full"] * 2 + ["sliding"] * 2
    assert a["window"] == 64


def share(held: int) -> dict:
    """The MoE file cut to `held` of its 8 experts, listed in reduced."""
    return dict(data("qwen2_moe_small"), num_experts=held,
                reduced=["num_experts"], published={"num_experts": 8})


def test_expert_share_sets_the_router_width():
    a = spec.arch_of(share(4))
    assert (a["experts_total"], a["experts_held"]) == (8, 4)


@dataclasses.dataclass(frozen=True)
class _Fields:
    """The fields a program may add under the names the key table gives."""
    n_experts_held: int = 0
    norm_topk_prob: bool = True
    shared_expert_gate: bool = True
    layer_types: tuple = ()


# file, the ModelConfig field it needs, the key a refusal names, the value
MIXED = ("sliding_attention",) * 3 + ("full_attention",)
NEEDS_A_FIELD = {
    "held experts": (share(4), "n_experts_held", "num_experts", 4),
    "top-k weights not renormalised": (
        dict(data("qwen2_moe_small"), norm_topk_prob=False),
        "norm_topk_prob", "norm_topk_prob", False),
    "ungated shared experts": (
        {**{k: v for k, v in data("qwen2_moe_small").items()
            if k != "shared_expert_intermediate_size"},
         "n_shared_experts": 2}, "shared_expert_gate", "n_shared_experts",
        False),
    "window and full layers mixed": (
        dict(data("window_small"), layer_types=list(MIXED)), "layer_types",
        "layer_types", MIXED),
}


@pytest.mark.parametrize("case", sorted(NEEDS_A_FIELD))
def test_refused_without_the_program_field(case, monkeypatch):
    """Refused, naming the key, by a program without the field; set from
    the file in a program that has it."""
    import repro.configs
    from repro.configs.base import ModelConfig
    cfg, field, key, value = NEEDS_A_FIELD[case]
    if program_has(field):
        assert getattr(spec.model_config(cfg), field) == value
    else:
        with pytest.raises(spec.Refused, match=key):
            spec.model_config(cfg)
    extended = dataclasses.make_dataclass(
        "Extended", [(f.name, f.type, dataclasses.field(default=f.default))
                     for f in dataclasses.fields(_Fields)
                     if not program_has(f.name)],
        bases=(ModelConfig,), frozen=True)
    real = repro.configs.get_config
    monkeypatch.setattr(repro.configs, "get_config", lambda name: extended(
        **{f.name: getattr(real(name), f.name)
           for f in dataclasses.fields(ModelConfig)}))
    assert getattr(spec.model_config(cfg), field) == value


REFUSED = {
    "unknown key": (dict(data("qwen2_moe_small"), scoring_func="sigmoid"),
                    "scoring_func"),
    "one shape given twice": (
        dict(data("qwen2_moe_small"), n_routed_experts=8), "n_routed_experts"),
    "MoE file without norm_topk_prob": (
        {k: v for k, v in data("qwen2_moe_small").items()
         if k != "norm_topk_prob"}, "norm_topk_prob"),
    "shared width not whole experts": (
        dict(data("qwen2_moe_small"), shared_expert_intermediate_size=48),
        "shared_expert_intermediate_size"),
    "reduced without published": (
        dict(share(4), published={}), "num_experts"),
    "yarn rotation": (
        dict(data("window_small"), rope_parameters={
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16}),
        "rope_parameters"),
    "rope scaling": (dict(data("window_small"), rope_scaling={
        "type": "dynamic", "factor": 2.0}), "rope_scaling"),
    "gelu MLP": (dict(data("window_small"), hidden_act="gelu"),
                 "hidden_act"),
    "sliding layers without a window": (
        dict(data("window_small"), sliding_window=None), "sliding_window"),
    "layer_types of another length": (
        dict(data("window_small"), layer_types=["full_attention"]),
        "layer_types"),
    "linear attention layer": (
        dict(data("window_small"), layer_types=["linear_attention"] * 4),
        "layer_types"),
    "mlp_layer_types against decoder_sparse_step": (
        dict(data("qwen2_moe_small"),
             mlp_layer_types=["dense", "sparse", "sparse", "sparse"]),
        "mlp_layer_types"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_refused_naming_the_key(case):
    cfg, key = REFUSED[case]
    with pytest.raises(spec.Refused, match=key):
        spec.model_config(copy.deepcopy(cfg))


@pytest.mark.parametrize("name", ["qwen2_moe_small", "window_small"])
def test_dense_reference_refuses_other_layers(name):
    from bench.references import dense
    a = spec.arch_of(data(name))
    with pytest.raises(spec.Refused, match="dense reference"):
        dense.check(a)
    with pytest.raises(spec.Refused, match="dense reference"):
        dense.served_gaps(a, 1, [])
    dense.check(ARCH["internlm2_1_8b"])


def test_harness_refuses_before_the_run(monkeypatch):
    """A file the reference cannot check stops the run before any weight
    is made."""
    from bench import run as R
    from bench.tests import small
    args, w, _ = small.cell()
    monkeypatch.setattr(R, "make_params", lambda *a: pytest.fail("ran"))
    with pytest.raises(spec.Refused, match="dense reference"):
        R.run(args, require_chip=False, workload=w,
              config=data("qwen2_moe_small"))
