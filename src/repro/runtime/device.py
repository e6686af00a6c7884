"""Which device a run is on, and where its compiled programs are cached.

Both entry points that run on the chip — `chip_smoke.py` and
`repro.launch.serve` — call `setup_compile_cache` before their first
compile. `require_tpu` is the chip path's guard: JAX falls back to the CPU
when the TPU runtime fails to start, and a run that silently lands there
must fail instead of reporting results under the chip's name.
"""
from __future__ import annotations

import os
import pathlib

import jax

# one fixed directory inside the checkout: the cache key includes the
# path, so a directory named after a temp dir, pid or time never hits
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at `CACHE_DIR`.
    Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """The device as JAX reports it: platform, device_kind, count."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """`device_info`, or RuntimeError when JAX found no TPU."""
    info = device_info()
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX runs on {info['platform']} "
                           f"({info['kind']}, {info['count']} device(s))")
    return info
