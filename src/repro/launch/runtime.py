"""Process set-up shared by the launchers and ``chip_smoke.py``: JAX's
persistent compilation cache, and the devices a run is on.

Nothing here runs at import; entry points call these functions.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache: a fixed path, so that each run of a checkout finds what
# the previous one compiled (gitignored).
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    this sets nothing. Otherwise the cache goes to ``REPO_CACHE_DIR``. Call
    it before the first compile: JAX settles the cache once per process."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def describe_devices(tag: str) -> dict:
    """Print, under ``[tag]``, and return the platform, kind and count of
    the devices JAX runs on."""
    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[{tag}] devices: {dev['platform']} {dev['kind']} "
          f"x{dev['count']}", flush=True)
    return dev
