"""Process-level JAX set-up shared by every entry point: which platform
the process runs on and where its compiled programs are cached.

``force_platform`` sets the env vars (so child processes inherit the
choice, and ``--devices N`` can size the virtual CPU mesh through
``XLA_FLAGS``) AND applies ``jax.config.update("jax_platforms", ...)``
before first device access, then refuses loudly if a backend was already
up on some other platform. A forced platform is a hard requirement:
``force_platform("tpu")`` on a host where no TPU comes up makes jax raise
``Unable to initialize backend 'tpu'`` at the first device access instead
of carrying on on the CPU, which is what an unforced (``auto``) process
does.
"""

from __future__ import annotations

import os
import pathlib
import re
import sys


def force_platform(name: str, n_devices: int | None = None) -> None:
    """Pin ``jax_platforms`` to ``name``; optionally force ``n_devices``
    virtual host devices (CPU platform only).

    Must run before the first JAX device access in this process. If jax's
    backend is already initialized the config update cannot take effect —
    that is reported loudly rather than silently proceeding on the wrong
    platform.
    """
    os.environ["JAX_PLATFORMS"] = name
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        # replace any pre-existing count: a stale smaller value would
        # starve the mesh this process is about to build
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\S+", "", flags
        )
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    jax.config.update("jax_platforms", name)

    if _backend_already_initialized():
        devs = jax.devices()
        plats = {d.platform for d in devs}
        if plats != {name} or (
            n_devices is not None and len(devs) < n_devices
        ):
            raise RuntimeError(
                f"force_platform({name!r}, n_devices={n_devices}) called "
                f"after JAX initialized {len(devs)} {sorted(plats)} "
                "device(s); it must run before first device access"
            )


def _backend_already_initialized() -> bool:
    """True iff some jax backend has been brought up in this process
    (device queries would no longer honor a config change)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    if xb is None:
        return False
    probe = getattr(xb, "backends_are_initialized", None)
    if probe is not None:
        return bool(probe())
    return bool(getattr(xb, "_backends", None))


def use_compile_cache() -> str:
    """Give this process jax's persistent compilation cache at a path that
    does not move between runs (the path is part of every cache key), and
    return it. ``JAX_COMPILATION_CACHE_DIR`` wins when set — jax reads it
    itself and nothing is touched here; otherwise the cache lives in
    ``.jax_cache/`` at the root of this checkout. Call at the top of an
    entry point, before its first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
