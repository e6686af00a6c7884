"""A small cell the CPU tests run through the whole harness: the
internlm2 configuration cut to two narrow layers and a short context,
with a sessions mix scaled to fit."""
import argparse
import copy
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]

# widest_gap limit at this size: the int8 program read 0.015-0.038 over
# seeds 1-4 on the CPU, the int4 control 0.32-0.49
LIMIT = 0.15


def cell(heads=4, kv_heads=2, bias=False, seconds=2.5, seed=1):
    cfg = json.loads((BENCH / "configs" / "internlm2_1_8b.json").read_text())
    cfg.update(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
               num_attention_heads=heads, num_key_value_heads=kv_heads,
               head_dim=32, vocab_size=512, kv_page_tokens=16,
               attention_bias=bias)
    w = {"name": "small", "config": "small", "chips": 1, "batch": 3,
         "max_len": 256, "clients": 3,
         "traffic": {"generator": "sessions", "history_frac": [0.5, 0.75],
                     "new_tokens": [8, 24], "output_tokens": [8, 24]},
         "limits": {"widest_gap": LIMIT}, "warmup_max_s": 60}
    args = argparse.Namespace(workload="small", seed=seed, seconds=seconds,
                              trace=0, trace_dir=None, kv_dtype=None)
    return args, copy.deepcopy(w), cfg


def run(monkeypatch, kv_dtype=None, **kw):
    """One harness run of the small cell on the CPU (the chip check
    skipped), with a short warm-up."""
    from bench import run as R
    monkeypatch.setattr(R, "QUIET_TICKS", 10)
    args, w, cfg = cell(**kw)
    return R.run(args, require_chip=False, workload=w, config=cfg,
                 kv_dtype=kv_dtype)
