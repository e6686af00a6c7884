"""Which device a run is on, its published peaks, and the compile cache.

Kept with the benchmark so that no change to the program can move the
yardstick: the chip check, the table of peaks and the compile counter are
read here, never from the code under test.
"""
from __future__ import annotations

import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
# one fixed directory inside the checkout: the path is part of the cache
# key, so a directory named after a temp dir, pid or time never hits
CACHE_DIR = ROOT / ".jax_cache" / "bench"


def setup_compile_cache() -> str:
    """Keep every compiled program in `CACHE_DIR`, however short its
    compile, so that a second run of a cell finds all of them there."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def device_info() -> dict:
    """The device as JAX reports it: platform, device_kind, count."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    """`device_info`, or RuntimeError when JAX found no TPU or fewer chips
    than the cell asks for."""
    info = device_info()
    if info["platform"] != "tpu":
        raise RuntimeError(f"no TPU: JAX runs on {info['platform']} "
                           f"({info['kind']}, {info['count']} device(s))")
    if info["count"] < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees "
                           f"{info['count']}")
    return info


def peaks(kind: str) -> dict:
    """Published peaks of one chip of `kind` (bench/peaks.json). A device
    that is not in the table is an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest local device, where the backend
    reports it."""
    import jax
    got = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in jax.local_devices()]
    got = [g for g in got if g is not None]
    return max(got) if got else None


class CompileCounter:
    """Counts programs traced or compiled in this process, and the seconds
    spent compiling, from JAX's monitoring events. A persistent-cache hit
    still traces and lowers, so it counts as a new program too."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.traces, self.backend, self.seconds = 0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self._EVENTS[0]:
            self.traces += 1
        elif event == self._EVENTS[1]:
            self.backend += 1
            self.seconds += duration

    @property
    def n(self) -> int:
        return self.traces + self.backend
