"""Process set-up shared by the command-line entry points.

Both helpers change the whole process, so only a script's `main` calls
them, never an import of `repro`.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The persistent compilation cache when the environment names none: a
#: fixed path in the checkout (the path is part of the cache key, so a
#: directory that moves never hits). Listed in .gitignore.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def force_host_devices(argv) -> None:
    """Give a CPU run of ``--shards N`` its N host devices. jax fixes
    the device count when it first initializes, so call this before
    jax is imported. A caller's own XLA_FLAGS win. The flag shapes only
    the CPU platform: on an accelerator, jax.devices() is still the
    chips."""
    if "--shards" in argv and "XLA_FLAGS" not in os.environ:
        n = int(argv[argv.index("--shards") + 1])
        if n > 1:
            os.environ["XLA_FLAGS"] = \
                f"--xla_force_host_platform_device_count={n}"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs: where
    JAX_COMPILATION_CACHE_DIR is set, jax already uses it and nothing is
    set here; otherwise the cache goes to `CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
