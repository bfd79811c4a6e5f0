"""The chip a run measures: refusal of anything but a TPU, the device record
every result carries, the peaks table, and JAX's compile events.

`CompileMeter` follows the one in `chip_smoke.py`: the benchmark keeps its
own, so no later change to the program can change how it counts."""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChipError(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The devices a cell runs on; raises `NoChipError` on any other
    platform or on too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChipError(f"cell asks for {chips} chips, JAX found "
                          f"{len(devs)}")
    return devs[:chips]


def device_record(devs) -> dict:
    """platform, kind, count and the peak bytes in use on the fullest chip
    (read before the reference check runs, which would set its own)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of `kind`; an unknown kind is an error."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{', '.join(sorted(table))}")
    return table[kind]


class CompileMeter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (a load from the persistent cache is reported as a compile)."""

    def __init__(self) -> None:
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def fresh(self) -> int:
        """Compiles so far that the persistent cache did not serve."""
        return self.compiles - self.cache_hits
