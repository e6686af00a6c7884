"""Finds a cell's pieces by name: `workloads/<cell>.json` names its
configuration (`configs/<config>.json`, whose `reference` names its plain
reference, `references/<reference>.py`) and its traffic mix
(`traffic/<mix>.json`, whose `generator` names `traffic/<generator>.py`);
`BENCHMARK.json` at the checkout's root lists the metrics, each read by
`metrics/<metric>.py`.

A configuration file reaches the reference and the counts as `arch_of`'s
shapes and the program as `model_config`'s `ModelConfig`, both through one
key table, `KEY_TABLE`; a key that neither it nor `DOCUMENTARY` holds is
refused, as is a value the program or the yardstick cannot take."""
from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def workload(name: str) -> dict:
    w = _json(BENCH / "workloads" / f"{name}.json")
    w["name"] = name
    return w


def config(name: str) -> dict:
    return _json(BENCH / "configs" / f"{name}.json")


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def metrics_for(cell: str, kind: str) -> list[dict]:
    """The metrics of `kind` ("end_to_end" or "per_layer") this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in benchmark()[kind]
            if cell in m.get("workloads", [cell])]


def reference(cfg: dict):
    """`references/<reference>.py`: its ``served_gaps(arch, seed,
    sequences)`` is what `correct` compares with, and its ``check(arch)``
    raises `Refused` for shapes it does not model."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def reader(metric: str):
    """`metrics/<metric>.py`: its ``read(ctx)`` returns the number, or
    None where the run gave it nothing to read."""
    return importlib.import_module(f"bench.metrics.{metric}")


class Refused(RuntimeError):
    """A configuration file the harness cannot hand over faithfully to the
    program, the reference or the counts. The message names the key."""


EXPERT_KEYS = ("num_experts", "n_routed_experts", "num_local_experts")
SHARED_KEYS = ("shared_expert_intermediate_size", "n_shared_experts",
               "num_shared_experts")

# The key table: every key of a configuration file that sets a shape, with
# its name in `arch` (what the reference and `flops.py` compute with) and
# the `ModelConfig` field it sets; None where it has none. Keys use the
# names of the model's own config.json. A file holding a key that is
# neither here nor in DOCUMENTARY is refused, never ignored.
KEY_TABLE = (
    # dense decoder
    (("num_hidden_layers",), "layers", "n_layers"),
    (("hidden_size",), "d_model", "d_model"),
    (("num_attention_heads",), "heads", "n_heads"),
    (("num_key_value_heads",), "kv_heads", "n_kv_heads"),
    (("head_dim",), "head_dim", "head_dim"),
    (("intermediate_size",), "d_ff", "d_ff"),
    (("vocab_size",), "vocab", "vocab"),
    (("attention_bias",), "qkv_bias", "qkv_bias"),
    (("rope_theta", "rope_parameters"), "rope_theta", "rope_theta"),
    (("rope_scaling",), None, None),           # must be absent or null
    (("rms_norm_eps",), "rms_norm_eps", "norm_eps"),
    (("hidden_act",), None, None),             # must be "silu" (SwiGLU)
    (("tie_word_embeddings",), None, "tie_embeddings"),
    (("torch_dtype",), None, "dtype"),
    (("kv_page_tokens",), "page", "quant"),
    (("kv_cache_dtype",), "kv_dtype", None),   # the engine's pool dtype
    # routed experts: the count is of the experts held here; where it is
    # in `reduced`, `published` gives the router's width (n_experts)
    (EXPERT_KEYS, "experts_total", "n_experts"),
    (EXPERT_KEYS, "experts_held", "n_experts_held"),
    (("num_experts_per_tok",), "top_k", "top_k"),
    (("norm_topk_prob",), "norm_topk", "norm_topk_prob"),
    (("moe_intermediate_size",), "moe_d_ff", "moe_d_ff"),
    # shared experts, as one total width: Qwen2-MoE's gated width, or
    # DeepSeek's ungated count of experts moe_intermediate_size wide
    (SHARED_KEYS, "shared_d_ff", "n_shared_experts"),
    (SHARED_KEYS, "shared_gate", "shared_expert_gate"),
    # which layers hold experts
    (("first_k_dense_replace", "num_dense_layers"), "dense_layers",
     "block_pattern"),
    (("decoder_sparse_step", "mlp_only_layers", "mlp_layer_types"),
     "layer_kinds", "block_pattern"),
    # attention windows
    (("sliding_window", "use_sliding_window"), "window", "sliding_window"),
    (("layer_types", "max_window_layers"), "layer_kinds", "layer_types"),
)

# keys that set nothing the program serves or the yardstick counts: the
# benchmark's own notes, and config.json keys for training, tokenizers and
# the context limit
DOCUMENTARY = frozenset({
    "name", "source", "paper", "registry", "reference", "deployment",
    "reduced", "published", "why_reduced", "assumed",
    "architectures", "model_type", "transformers_version",
    "max_position_embeddings", "bos_token_id", "eos_token_id",
    "pad_token_id", "initializer_range", "use_cache", "attention_dropout",
    "output_router_logits", "router_aux_loss_coef"})

KNOWN = DOCUMENTARY | {k for keys, _, _ in KEY_TABLE for k in keys}


def _pick(cfg: dict, keys: tuple, default=None):
    """(key, value) of the one key of `keys` the file holds, or (None,
    default)."""
    held = [k for k in keys if k in cfg]
    if len(held) > 1:
        raise Refused(f"{' and '.join(held)}: one shape given twice")
    return (held[0], cfg[held[0]]) if held else (None, default)


def _rope_theta(cfg: dict) -> float:
    key, rope = _pick(cfg, ("rope_theta", "rope_parameters"))
    if key is None:
        raise Refused("rope_theta: the file states no rotary base")
    if key == "rope_theta":
        return float(rope)
    # one plain rotation for every kind of layer, or none that is modelled
    groups = [rope] if "rope_type" in rope or "rope_theta" in rope \
        else list(rope.values())
    thetas = {g.get("rope_theta") for g in groups}
    if any(set(g) - {"rope_type", "rope_theta"} or
           g.get("rope_type", "default") != "default" for g in groups) \
            or len(thetas) != 1 or None in thetas:
        raise Refused(f"rope_parameters: only one plain rope_theta is "
                      f"modelled, not {rope}")
    return float(thetas.pop())


def _experts(cfg: dict) -> dict:
    key, held = _pick(cfg, EXPERT_KEYS, 0)
    held = held or 0
    total = cfg["published"][key] if key in cfg.get("reduced", ()) \
        else held
    none = {"experts_total": 0, "experts_held": 0, "top_k": 0,
            "moe_d_ff": 0, "shared_d_ff": 0, "shared_gate": False,
            "norm_topk": False}
    if not total:
        return none
    for k in ("num_experts_per_tok", "moe_intermediate_size",
              "norm_topk_prob"):
        if k not in cfg:
            raise Refused(f"{k}: a file with experts states it")
    top_k, moe_d_ff = cfg["num_experts_per_tok"], cfg["moe_intermediate_size"]
    if not 0 < held <= total or not 0 < top_k <= total:
        raise Refused(f"{key}, num_experts_per_tok: {held} held of {total} "
                      f"with {top_k} per token")
    skey, shared = _pick(cfg, SHARED_KEYS, 0)
    gated = skey == "shared_expert_intermediate_size"
    shared_d_ff = shared if gated else (shared or 0) * moe_d_ff
    return {"experts_total": total, "experts_held": held, "top_k": top_k,
            "moe_d_ff": moe_d_ff, "shared_d_ff": shared_d_ff,
            "shared_gate": gated and shared_d_ff > 0,
            "norm_topk": bool(cfg["norm_topk_prob"])}


def _ffn_kinds(cfg: dict, layers: int, experts: int) -> list:
    """Each layer's FFN, "moe" or "dense", placed as Qwen2-MoE
    (decoder_sparse_step, mlp_only_layers), DeepSeek (first_k_dense_replace)
    or a per-layer list (mlp_layer_types) places it; these must agree."""
    rules = ("first_k_dense_replace", "num_dense_layers",
             "decoder_sparse_step", "mlp_only_layers")
    _, first = _pick(cfg, rules[:2], 0)
    step = cfg.get("decoder_sparse_step", 1)
    only = set(cfg.get("mlp_only_layers", ()))
    kinds = ["moe" if experts and i >= first and (i + 1) % step == 0
             and i not in only else "dense" for i in range(layers)]
    if "mlp_layer_types" in cfg:
        given = cfg["mlp_layer_types"]
        stated = [{"sparse": "moe", "dense": "dense"}.get(t) for t in given]
        if len(given) != layers or None in stated \
                or ("moe" in stated and not experts) \
                or (any(k in cfg for k in rules) and stated != kinds):
            raise Refused(f"mlp_layer_types: {given} does not fit "
                          f"{layers} layers, {experts} experts and the "
                          f"other keys that place them")
        kinds = stated
    return kinds


def _attention_kinds(cfg: dict, layers: int):
    """(window, "full" or "sliding" for each layer)."""
    window = cfg.get("sliding_window") if cfg.get("use_sliding_window",
                                                  True) else None
    if "layer_types" in cfg:
        given = cfg["layer_types"]
        kinds = [{"full_attention": "full",
                  "sliding_attention": "sliding"}.get(t) for t in given]
        if len(given) != layers or None in kinds:
            raise Refused(f"layer_types: {given} is not {layers} of "
                          f"full_attention and sliding_attention")
    else:
        start = cfg.get("max_window_layers", 0)
        kinds = ["sliding" if window is not None and i >= start else "full"
                 for i in range(layers)]
    if "sliding" not in kinds:
        return None, kinds
    if not isinstance(window, int) or window < 1:
        raise Refused(f"sliding_window: sliding layers need a window, "
                      f"not {window!r}")
    return window, kinds


def arch_of(cfg: dict) -> dict:
    """The shapes the yardstick computes with, from a configuration file.
    Refuses a file with a key it does not know, or one whose value the
    reference and the counts cannot model."""
    unknown = sorted(set(cfg) - KNOWN)
    if unknown:
        raise Refused(f"{', '.join(unknown)}: not a key the harness reads "
                      f"(bench/spec.py KEY_TABLE, DOCUMENTARY)")
    missing = [k for k in cfg.get("reduced", ())
               if k not in cfg.get("published", {})]
    if missing:
        raise Refused(f"{', '.join(missing)}: in reduced without its "
                      f"published value")
    if cfg.get("rope_scaling") is not None:
        raise Refused(f"rope_scaling: {cfg['rope_scaling']} is not modelled")
    if cfg.get("hidden_act", "silu") != "silu":
        raise Refused(f"hidden_act: {cfg['hidden_act']}; the MLP counted "
                      f"and served is SwiGLU (silu)")
    heads, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    a = {
        "layers": layers,
        "d_model": cfg["hidden_size"],
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", cfg["hidden_size"] // heads),
        "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "qkv_bias": bool(cfg.get("attention_bias", False)),
        "rope_theta": _rope_theta(cfg),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "page": cfg["kv_page_tokens"],
        "kv_dtype": cfg["kv_cache_dtype"],
    }
    a.update(_experts(cfg))
    ffn = _ffn_kinds(cfg, layers, a["experts_total"])
    a["window"], attn = _attention_kinds(cfg, layers)
    a["dense_layers"] = next((i for i, k in enumerate(ffn) if k == "moe"),
                             layers)
    a["layer_kinds"] = tuple(zip(attn, ffn))
    return a


_NEEDED = object()     # a field every program has to have


def program_fields(cfg: dict) -> list:
    """(field, value, the file's key, the value of a program without the
    field) for every `ModelConfig` field the key table names."""
    from repro.core.quantization import QuantConfig
    a = arch_of(cfg)
    ekey = _pick(cfg, EXPERT_KEYS)[0] or EXPERT_KEYS[0]
    skey = _pick(cfg, SHARED_KEYS)[0] or SHARED_KEYS[0]
    shared = 0
    if a["shared_d_ff"]:
        shared, rest = divmod(a["shared_d_ff"], a["moe_d_ff"])
        if rest:
            raise Refused(f"{skey}: the program's shared experts are whole "
                          f"multiples of moe_intermediate_size wide")
    ffn = ["moe" if f == "moe" else "attn" for _, f in a["layer_kinds"]]
    period = next(p for p in range(1, a["layers"] + 1)
                  if all(k == ffn[i % p] for i, k in enumerate(ffn)))
    attn = tuple(f"{k}_attention" for k, _ in a["layer_kinds"])
    moe = a["experts_total"] > 0
    # a program without `layer_types` windows every layer or none
    return [
        ("n_layers", a["layers"], "num_hidden_layers", _NEEDED),
        ("d_model", a["d_model"], "hidden_size", _NEEDED),
        ("n_heads", a["heads"], "num_attention_heads", _NEEDED),
        ("n_kv_heads", a["kv_heads"], "num_key_value_heads", _NEEDED),
        ("head_dim", a["head_dim"], "head_dim", _NEEDED),
        ("d_ff", a["d_ff"], "intermediate_size", _NEEDED),
        ("vocab", a["vocab"], "vocab_size", _NEEDED),
        ("qkv_bias", a["qkv_bias"], "attention_bias", _NEEDED),
        ("rope_theta", a["rope_theta"], "rope_theta", _NEEDED),
        ("norm_eps", a["rms_norm_eps"], "rms_norm_eps", 1e-6),
        ("tie_embeddings", bool(cfg.get("tie_word_embeddings", False)),
         "tie_word_embeddings", _NEEDED),
        ("dtype", cfg.get("torch_dtype", "bfloat16"), "torch_dtype", _NEEDED),
        ("quant", QuantConfig(granularity="per_block", block_size=a["page"]),
         "kv_page_tokens", _NEEDED),
        ("n_experts", a["experts_total"], ekey, _NEEDED),
        ("n_experts_held", a["experts_held"], ekey, a["experts_total"]),
        ("top_k", a["top_k"], "num_experts_per_tok", _NEEDED),
        ("norm_topk_prob", a["norm_topk"], "norm_topk_prob",
         True if moe else a["norm_topk"]),
        ("moe_d_ff", a["moe_d_ff"] or None, "moe_intermediate_size", _NEEDED),
        ("n_shared_experts", shared, skey, _NEEDED),
        ("shared_expert_gate", a["shared_gate"], skey, shared > 0),
        ("block_pattern", tuple(ffn[:period]), "mlp_layer_types", _NEEDED),
        ("sliding_window", a["window"], "sliding_window", _NEEDED),
        ("layer_types", attn, "layer_types",
         attn if len(set(attn)) == 1 else _NEEDED),
    ]


def model_config(cfg: dict):
    """The program's `ModelConfig` for a configuration file: the registry
    entry it names (family, kernel defaults), with every field the key
    table names set from the file. A field the program lacks is left out
    where its value is what the program does without it, and refused,
    naming the file's key, where it is not."""
    from repro.configs import get_config
    rows = program_fields(cfg)
    base = get_config(cfg["registry"])
    have = {f.name for f in dataclasses.fields(base)}
    changes = {}
    for field, value, key, without in rows:
        if field in have:
            changes[field] = value
        elif without is _NEEDED or value != without:
            raise Refused(f"{key}: the program has no ModelConfig field "
                          f"{field!r} to take {value!r}")
    return dataclasses.replace(base, **changes)
