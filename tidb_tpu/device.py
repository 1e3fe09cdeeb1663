"""The process's accelerator: which device it is on, and where compiled
programs are kept.

One process owns a chip, and JAX picks its backend lazily and — with
JAX_PLATFORMS unset — falls back to the CPU with only a warning when the
chip is missing or held. So "device" in an engine tag or a benchmark line
means nothing until the platform behind it is stated. `describe()` is the
one place product code asks JAX what it is running on: the first call
initialises the backend and logs the device line; `described()` answers
for scrapes, which must never initialise a backend themselves.
"""

from __future__ import annotations

import logging
import os
import threading
from pathlib import Path
from typing import Optional

_log = logging.getLogger("tidb_tpu.device")

# the JAX backend is process-global; this mirrors the one fact about it
_info: Optional[dict] = None
_info_lock = threading.Lock()

_CHECKOUT = Path(__file__).resolve().parent.parent


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    JAX_COMPILATION_CACHE_DIR, when set, is read by JAX itself and nothing
    here overrides it. Otherwise the cache lives at a FIXED path inside
    the checkout: the directory is part of the cache key, so a temp, pid
    or time based path would never hit. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe() -> dict:
    """{"platform", "device_kind", "count"} as JAX reports them.
    Initialises the backend on first call and logs one device line."""
    global _info
    with _info_lock:
        if _info is None:
            import jax

            devs = jax.devices()
            _info = {"platform": devs[0].platform,
                     "device_kind": devs[0].device_kind,
                     "count": len(devs)}
            _log.info("jax backend up: %s", line(_info))
        return dict(_info)


def described() -> Optional[dict]:
    """describe() if some caller already initialised the backend, else
    None — never touches JAX (a /status scrape must not grab the TPU)."""
    with _info_lock:
        return None if _info is None else dict(_info)


def line(info: dict) -> str:
    return (f"platform={info['platform']} "
            f"device_kind={info['device_kind']!r} count={info['count']}")

