"""End-to-end behaviour tests for the paper's system.

System-level invariants: training converges on structured synthetic data,
the serving path generates coherently with the INT8 cache, quantized-cache
serving matches unquantized within the paper's error model, and the
launchers run.
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.training.step import init_opt_state, make_train_step

REPO = pathlib.Path(__file__).resolve().parents[1]

jax.config.update("jax_platform_name", "cpu")


def test_training_reduces_loss():
    """~30 steps on copy-structured synthetic data must cut loss by >15%."""
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(
        cfg, AdamWConfig(lr=1e-3, warmup_steps=3, total_steps=40)))
    data = SyntheticLM(DataConfig(seq_len=64, global_batch=8, vocab=cfg.vocab,
                                  seed=1))
    losses = []
    for i in range(30):
        params, opt, m = step(params, opt,
                              {k: jnp.asarray(v) for k, v in
                               data.batch_at(i).items()})
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.85 * losses[0], (losses[0], losses[-1])
    assert all(np.isfinite(losses))


def test_training_with_grad_compression_tracks_uncompressed():
    """INT8 gradient compression (error feedback) stays close to the
    uncompressed trajectory — the paper's technique on the DP wire."""
    cfg = get_config("internlm2_1_8b", smoke=True)
    data = SyntheticLM(DataConfig(seq_len=32, global_batch=4, vocab=cfg.vocab,
                                  seed=2))
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20)

    def run(compress):
        p = T.init_params(cfg, jax.random.PRNGKey(0))
        o = init_opt_state(p, grad_compression=compress)
        s = jax.jit(make_train_step(cfg, ocfg, grad_compression=compress))
        for i in range(12):
            p, o, m = s(p, o, {k: jnp.asarray(v) for k, v in
                               data.batch_at(i).items()})
        return float(m["loss"])

    l_plain, l_comp = run(False), run(True)
    assert abs(l_plain - l_comp) / l_plain < 0.08, (l_plain, l_comp)


def test_quantized_vs_finer_cache_generation_agreement():
    """Greedy generations with coarse (paper per-channel) and fine
    (per-block-8) caches agree on most tokens — the paper's 'minimal impact
    on downstream behaviour' claim at system level.

    The model is briefly trained first: at random init the logit argmax
    margins are noise-level, so agreement between two quantizations was a
    coin flip (the historical 0.59-vs-0.7 flake). A few steps on the
    structured synthetic data sharpen the margins the claim presumes, and
    prompts are drawn from that training distribution."""
    import dataclasses
    from repro.core.quantization import QuantConfig
    from repro.serving import greedy_generate

    base = get_config("llama3_2_3b", smoke=True)
    params = T.init_params(base, jax.random.PRNGKey(3))
    opt = init_opt_state(params)
    step = jax.jit(make_train_step(
        base, AdamWConfig(lr=1e-3, warmup_steps=3, total_steps=40)))
    data = SyntheticLM(DataConfig(seq_len=64, global_batch=8,
                                  vocab=base.vocab, seed=1))
    for i in range(25):
        params, opt, _ = step(params, opt,
                              {k: jnp.asarray(v) for k, v in
                               data.batch_at(i).items()})
    prompts = jnp.asarray(data.batch_at(100)["tokens"][:4, :8])
    cfg_pc = dataclasses.replace(base, quant=QuantConfig(
        granularity="per_channel"))
    cfg_fine = dataclasses.replace(base, quant=QuantConfig(
        granularity="per_block", block_size=8))
    out_pc = greedy_generate(params, cfg_pc, prompts, steps=8)
    out_fine = greedy_generate(params, cfg_fine, prompts, steps=8)
    agreement = float(jnp.mean((out_pc == out_fine).astype(jnp.float32)))
    assert agreement >= 0.9, agreement


def test_microbatched_step_matches_full_batch():
    """Gradient accumulation is numerically equivalent to the full batch
    (same update up to f32 summation order)."""
    cfg = get_config("internlm2_1_8b", smoke=True)
    data = SyntheticLM(DataConfig(seq_len=32, global_batch=8, vocab=cfg.vocab,
                                  seed=5))
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=5)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

    def run(mb):
        p = T.init_params(cfg, jax.random.PRNGKey(0))
        o = init_opt_state(p)
        s = jax.jit(make_train_step(cfg, ocfg, microbatches=mb))
        p, o, m = s(p, o, batch)
        return p

    p1, p4 = run(1), run(4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        # bf16 params: one update step differs by at most ~1 bf16 quantum
        # (summation-order of the f32 microbatch accumulation)
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=2.5e-3)


def test_train_launcher_cli(tmp_path):
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch",
         "internlm2_1_8b", "--smoke", "--steps", "3", "--batch", "2",
         "--seq", "32", "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", str(REPO)),
             # without this the stripped env lets jax probe for a TPU
             # runtime and the subprocess stalls for minutes
             "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step" in r.stdout
    from repro.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 3


def test_serve_launcher_cli():
    """The serve CLI end-to-end, including the request-lifecycle flags
    (on-device sampling + stop string through the LLMEngine facade)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch",
         "internlm2_1_8b", "--smoke", "--requests", "4", "--max-new", "4",
         "--prompt-len", "8", "--max-len", "64", "--paged",
         "--temperature", "0.8", "--top-k", "20", "--top-p", "0.9",
         "--seed", "0", "--stop", "<511>"],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", str(REPO)),
             "JAX_PLATFORMS": "cpu"}, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "completed 4/4" in r.stdout
    assert "lifecycle" in r.stdout
