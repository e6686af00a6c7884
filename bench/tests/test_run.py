"""The harness end to end on the CPU, and its refusal without a TPU."""
import json
import os
import pathlib
import subprocess
import sys

from bench.tests import small

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_refused_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2_1_8b.sessions", "--seed", str(2**33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_small_cell_end_to_end(monkeypatch):
    res = small.run(monkeypatch, seed=2**33 + 3)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_gap"]["value"] < small.LIMIT
    m = res["metrics"]
    assert set(m) == {"setup_s", "ttft_p90_s", "tpot_p90_ms",
                      "output_tok_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert res["device"]["platform"] == "cpu"
    json.dumps(res)


def test_configs_name_their_reference_and_mix_its_generator():
    """A cell's pieces are found by name: each configuration's `reference`
    and each mix's `generator` is a module of the benchmark."""
    from bench import spec, traffic_gen
    for cell in spec.benchmark()["workloads"]:
        w = spec.workload(cell["name"])
        ref = spec.reference(spec.config(w["config"]))
        assert callable(ref.served_gaps) and callable(ref.check)
        mix = traffic_gen.load(w["traffic"])
        tr = traffic_gen.make(mix, vocab=1000, max_len=w["max_len"],
                              clients=2, page=256, seed=1)
        assert all(callable(getattr(tr, f))
                   for f in ("opening", "poll", "finished"))
