"""Small helpers the harness keeps for itself, so that no change to the
program can move them: the finished check, the device tag, and a count of
the programs compiled in the window."""
from __future__ import annotations

import numpy as np


def unfinished(finished) -> int:
    """Flows (or ring steps) that hit the horizon sentinel.  Their
    completion time is the horizon, not a measurement, so every configuration
    states that there are none."""
    return int(np.size(finished) - np.count_nonzero(np.asarray(finished)))


def device_tag(devices) -> dict:
    """The result line's `device` block, as JAX reports the devices used;
    `memory_peak_bytes` is the peak of the fullest one."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent cache:
    in all (`total`, of which `cache_hits` loaded), and while `active` is
    set (`count`: a window that compiles measures the compiler)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.active = False
        self.count = self.total = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self.EVENT:
            self.total += 1
            self.count += self.active

    def _on_event(self, event, **_):
        self.cache_hits += event == self.HIT
