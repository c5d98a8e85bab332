"""Persistent XLA compilation cache for the entry points.

First TPU compiles run tens of seconds to minutes (the ResNet-50 train
step, every serving bucket); with the cache a relaunch reloads the
executables in seconds.

Where the cache lives is decided from outside the program: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
names a directory.  Otherwise the cache is ``<checkout>/.jax_cache``,
derived from where this package sits — the directory is part of each
entry's key, so a path made from ``~``, a temporary name, a pid or the
clock would never hit on the next machine.
"""

from __future__ import annotations

import os
import pathlib

#: <checkout>/.jax_cache — the checkout is two levels above core/
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn the on-disk program cache on (idempotent); returns the
    directory in use, or None when ``DEEP_VISION_TPU_NO_COMPILE_CACHE=1``
    opted out (measuring true cold compiles)."""
    from deep_vision_tpu.obs import launch

    # every entry point calls this first: the launch record's ``outside``
    # ends here, and the first call is its ``cache`` stage
    record = launch.start()
    with record.once("cache"):
        import jax

        record.listen()
        if os.environ.get("DEEP_VISION_TPU_NO_COMPILE_CACHE"):
            return None
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            path = DEFAULT_DIR
            jax.config.update("jax_compilation_cache_dir", path)
        # only persist programs worth the disk round-trip
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)
        return path
