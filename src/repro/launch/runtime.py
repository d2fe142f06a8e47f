"""Process set-up shared by the launchers and ``chip_smoke.py``: the
persistent compile cache and the one-line device report.

Both run before the first compile. The cache follows
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
itself); otherwise it lives at one fixed, git-ignored path inside the
checkout, so a second run of the same programs from the same checkout
finds the first run's executables.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict

import jax

from repro.kernels.padding import resolve_interpret

REPO_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_report() -> Dict[str, Any]:
    """Backend, device kind, device count and the Pallas ``interpret`` flag
    the kernels will resolve to (raises on a backend with no lowering)."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "interpret": resolve_interpret()}


def announce(tag: str) -> Dict[str, Any]:
    """Enable the compile cache and print the device report (plus the cache
    directory) as ``tag``'s first line — what a launcher says before it
    compiles anything."""
    cache = enable_compile_cache()
    rep = dict(device_report(), compile_cache=cache)
    print(f"[{tag}] backend={rep['platform']} kind={rep['kind']!r} "
          f"devices={rep['count']} interpret={rep['interpret']} "
          f"compile_cache={cache}", flush=True)
    return rep
