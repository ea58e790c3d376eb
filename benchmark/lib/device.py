"""What the device and the compiler report: identity, memory, compiles."""

from __future__ import annotations

import threading


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n_devices: int) -> int:
    """``peak_bytes_in_use`` on the fullest of the first ``n_devices``
    chips; 0 where the backend reports nothing (the CPU)."""
    import jax
    peak = 0
    for d in jax.devices()[:n_devices]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Compile-cache requests of this process, from JAX's monitoring
    events. Every backend compile consults the persistent cache (it is
    always on in a benchmark run), so requests == programs compiled or
    read back; inside the measured window it must stay 0."""

    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    HIT = "/jax/compilation_cache/cache_hits"
    SECONDS = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.requests = self.hits = 0
        self.seconds = 0.0
        self._lock = threading.Lock()     # the engine thread compiles too
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.REQUEST or event == self.HIT:
            with self._lock:
                if event == self.REQUEST:
                    self.requests += 1
                else:
                    self.hits += 1

    def _on_secs(self, event: str, duration: float, **_kw) -> None:
        if event == self.SECONDS:
            with self._lock:
                self.seconds += duration

    def snapshot(self) -> dict:
        with self._lock:
            return {"requests": self.requests, "hits": self.hits,
                    "seconds": self.seconds}
