"""The chip path's guards, checked on the CPU: `chip_smoke.py` refuses to
run without a TPU, its phases pass at a small size through the same code,
the compile cache follows `JAX_COMPILATION_CACHE_DIR`, the kernel
dispatcher never yields interpret mode on a TPU, and a prefill chunk the
fused kernel cannot hold in VMEM is refused at engine construction."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.kernels import ops
from repro.kernels import quant_prefill as QP
from repro.runtime import device

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

jax.config.update("jax_platform_name", "cpu")

SMOKE_SERVE = ["--arch", "internlm2_1_8b", "--smoke", "--paged",
               "--prefix-cache", "--batch", "8", "--max-len", "64",
               "--max-new", "4"]


def test_chip_smoke_fails_without_tpu():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, cwd=REPO,
        env={"PATH": os.environ.get("PATH", "/usr/bin:/bin"),
             "HOME": os.environ.get("HOME", str(REPO)),
             "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_chip_smoke_kernel_parity_phase_interpreted():
    """Phase (b) at one row and one history page, the kernels in the
    interpreter: the same comparison the chip runs compiled."""
    errs = chip_smoke.kernel_parity("pallas_interpret", batch=1, pages=1)
    assert set(errs) == {f"{k}_{dt}" for k in ("decode", "prefill")
                         for dt in ("int8", "fp8_e4m3", "int4")}
    assert max(errs.values()) <= chip_smoke.PARITY_TOL


def test_chip_smoke_serve_phase_smoke_config():
    """Phase (c) on the smoke config: the late prompt hits the first
    prompt's chunk, every request runs to its budget, and the identical
    greedy pair agrees."""
    rep = chip_smoke.serve_phase(SMOKE_SERVE)
    assert rep["page_hits"] >= rep["chunk"] // 8
    assert rep["tokens"] == 8 * 4
    assert len(rep["prompt_lens"]) == 8
    assert rep["first_tokens"][2] == rep["first_tokens"][3]


def test_require_tpu_raises_on_cpu():
    with pytest.raises(RuntimeError, match="no TPU"):
        device.require_tpu()
    assert device.device_info()["platform"] == "cpu"


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert device.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = device.setup_compile_cache()
        assert fixed == str(REPO / ".jax_cache")
        assert fixed == device.setup_compile_cache()     # never moves
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_resolve_impl_never_interprets_on_tpu(monkeypatch):
    assert ops.resolve_impl("auto") == "xla"
    assert ops.resolve_impl("pallas_interpret") == "pallas_interpret"
    with pytest.raises(ValueError, match="unknown impl"):
        ops.resolve_impl("interpret")
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.resolve_impl("auto") == "pallas"
    with pytest.raises(ValueError, match="TPU"):
        ops.resolve_impl("pallas_interpret")


@pytest.mark.parametrize("group", [1, 2, 4, 8])
def test_prefill_vmem_bound_scales_with_group(group):
    """The largest chunk that fits halves as the GQA group doubles; the
    default 4-page chunk at page 256 fits every group up to 8."""
    QP.check_prefill_fits(group, 1024, 256, 128)
    fits = [c for c in (512, 1024, 2048, 4096, 8192)
            if QP.prefill_vmem_bytes(group, c, 256, 128)
            <= QP.PREFILL_VMEM_LIMIT]
    biggest = fits[-1]
    assert biggest * group in (4096, 8192)
    with pytest.raises(ValueError, match=f"at most {biggest} tokens"):
        QP.check_prefill_fits(group, 2 * biggest, 256, 128)


def test_engine_refuses_prefill_chunk_over_vmem():
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving import ContinuousBatcher, EngineConfig
    cfg = get_config("internlm2_1_8b", smoke=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="VMEM"):
        ContinuousBatcher(params, cfg, EngineConfig(
            batch=1, max_len=64, paged=True, prefill_chunk=1 << 20))
