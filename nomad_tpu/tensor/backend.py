"""JAX backend start-up for every process that schedules on a device.

One call, made before anything compiles (Server.__init__,
chip_smoke.py): it initializes the configured backend and lets a failure
raise — a scheduler that silently continues on another platform reports
numbers for hardware it is not running on — and it places the persistent
compile cache. A cold server compiles one keyed program per (rows,
placements, candidate-count) bucket at 13-18 s each on a v5e; a restart
should not pay that again.
"""

from __future__ import annotations

import os

# Fixed path inside the checkout (the path is part of the cache key, so a
# directory that moves never hits). JAX_COMPILATION_CACHE_DIR, when set,
# wins: JAX reads it itself and this module sets nothing.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def init_backend() -> list:
    """Initialize the configured JAX backend and return its devices.

    Raises the backend's own RuntimeError when the platform cannot
    initialize. Idempotent and cheap after the first call."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return jax.devices()


def device_info() -> dict:
    """{"platform", "kind", "count"} as JAX reports the devices — the
    label every printed result carries, so a number is never read apart
    from the hardware that produced it."""
    devices = init_backend()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
