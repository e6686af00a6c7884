"""Finds a cell's pieces by name: `workloads/<cell>.json` names its
configuration (`configs/<config>.json`, whose `reference` names its plain
reference, `references/<reference>.py`) and its traffic mix
(`traffic/<mix>.json`, whose `generator` names `traffic/<generator>.py`);
`BENCHMARK.json` at the checkout's root lists the metrics, each read by
`metrics/<metric>.py`."""
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
    sequences)`` is what `correct` compares with."""
    return importlib.import_module(f"bench.references.{cfg['reference']}")


def reader(metric: str):
    """`metrics/<metric>.py`: its ``read(ctx)`` returns the number, or
    None where the run gave it nothing to read."""
    return importlib.import_module(f"bench.metrics.{metric}")


def arch_of(cfg: dict) -> dict:
    """The shapes the yardstick computes with, from a configuration file."""
    heads = cfg["num_attention_heads"]
    return {
        "layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"],
        "heads": heads,
        "kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", cfg["hidden_size"] // heads),
        "d_ff": cfg["intermediate_size"],
        "vocab": cfg["vocab_size"],
        "qkv_bias": bool(cfg.get("attention_bias", False)),
        "rope_theta": float(cfg["rope_theta"]),
        "rms_norm_eps": float(cfg["rms_norm_eps"]),
        "page": cfg["kv_page_tokens"],
        "kv_dtype": cfg["kv_cache_dtype"],
    }


def model_config(cfg: dict):
    """The program's `ModelConfig` for a configuration file: the registry
    entry it names, with every size set from the file."""
    from repro.configs import get_config
    from repro.core.quantization import QuantConfig
    a = arch_of(cfg)
    base = get_config(cfg["registry"])
    return dataclasses.replace(
        base, n_layers=a["layers"], d_model=a["d_model"], n_heads=a["heads"],
        n_kv_heads=a["kv_heads"], head_dim=a["head_dim"], d_ff=a["d_ff"],
        vocab=a["vocab"], qkv_bias=a["qkv_bias"], rope_theta=a["rope_theta"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        dtype=cfg.get("torch_dtype", "bfloat16"),
        quant=QuantConfig(granularity="per_block", block_size=a["page"]))
