"""Which device a process runs on, and where its compiled programs go.

One process owns a chip: the driver (``ray_tpu.init()``, ``chip_smoke.py``,
``bench.py``). Workers, daemons and the head never do, and pin themselves
to the host CPU platform (``pin_cpu_env``). Tests and multi-chip dry runs
ask for the CPU explicitly, with N virtual devices (``force_cpu_platform``).
Nothing here falls back from one platform to another: a backend that
cannot initialize raises.
"""

from __future__ import annotations

import os
import re

_COUNT_FLAG = "--xla_force_host_platform_device_count"

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pin_cpu_env(n_devices: int | None = None) -> None:
    """Env-only half of the pin (no jax import): safe in fresh processes
    where jax has not been imported yet."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        repl = f"{_COUNT_FLAG}={n_devices}"
        if _COUNT_FLAG in flags:
            flags = re.sub(rf"{_COUNT_FLAG}=\d+", repl, flags)
        else:
            flags = (flags + " " + repl).strip()
        os.environ["XLA_FLAGS"] = flags


def force_cpu_platform(n_devices: int | None = None) -> None:
    """Pin jax to the host CPU platform, optionally with ``n_devices``
    virtual devices. Must run before the first backend touch
    (``jax.devices()`` etc.)."""
    pin_cpu_env(n_devices)

    import jax

    jax.config.update("jax_platforms", "cpu")
    if n_devices:
        jax.config.update("jax_num_cpu_devices", n_devices)


def on_chip(device=None) -> bool:
    """THE spelling of "this runs on the accelerator": ``device`` (default:
    the default backend's first device) is a TPU. Initializes the backend
    if nothing has yet; a backend that fails to initialize raises."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return device.platform == "tpu"


def chip_devices() -> list:
    """The default backend's TPU devices ([] on a CPU backend)."""
    import jax
    return [d for d in jax.devices() if on_chip(d)]


def pallas_interpret() -> bool:
    """Pallas kernels run under the interpreter only when the backend IS
    the CPU (tests: the same kernel logic, no Mosaic). Every other
    backend compiles them, and a kernel that does not lower fails there
    instead of quietly interpreting."""
    import jax
    return jax.default_backend() == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for a chip-owning entry
    point and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the location is JAX's
    business and nothing here overrides it; otherwise the cache lives at
    ``<checkout>/.jax_cache`` — fixed, never a temp name, a pid or a
    time: a run finds only what an earlier run left at the same path.
    The minimum-compile-time threshold drops to 0: the engine's programs
    (``_sample``, ``_insert``, ``_gather``) each compile in well under
    JAX's 1 s default and there are enough of them to matter on a cold
    machine. Call before the first compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
