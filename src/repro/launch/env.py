"""Process set-up shared by the launchers and ``chip_smoke.py``.

* :func:`enable_compile_cache` — JAX's persistent compilation cache at a
  place the caller can choose from outside.
* :func:`plan_target` — the hardware target the flow specializes for,
  from the device that is present.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch/env.py`` -> three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that directory from
    the environment itself and no other is set in code.  Otherwise the
    cache lives at the fixed path ``<checkout>/.jax_cache``: the path is
    part of the cache key, so it never moves (no temp name, pid or time).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def plan_target() -> str:
    """Target of the first visible device.

    A TPU is looked up by its ``device_kind`` (an unknown kind raises).
    On the CPU the launchers rehearse the plan of the default chip,
    ``tpu-v5e``: nothing the CPU runs is reported as a chip number.
    """
    from repro.hw.tpu import target_for_device
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        return target_for_device(dev)
    return "tpu-v5e"
