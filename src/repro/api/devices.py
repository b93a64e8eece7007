"""Host-device bootstrap shared by every entry point.

Fake host (CPU) devices for SPMD demos/tests are created via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which must be set
before the JAX backend initializes. This module therefore imports JAX
only *inside* the function, so ``from repro.api import
ensure_host_devices`` stays safe at the very top of a script.

The bootstrap also places JAX's persistent compilation cache, so a
repeated run of any entry point reads its compiled programs back: where
``$JAX_COMPILATION_CACHE_DIR`` is set, JAX uses it and nothing here
overrides it; otherwise the cache lives at a fixed path inside the
checkout, ``<checkout>/.jax_cache`` (a temporary or per-run directory
would never be found again by the next run).
"""

from __future__ import annotations

import os
import pathlib
import re

_FLAG = "--xla_force_host_platform_device_count"
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def _place_compile_cache() -> None:
    import jax

    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or jax.config.jax_compilation_cache_dir:
        return  # placed from outside: leave it alone
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def ensure_host_devices(n: int | None = None, *, default: int = 8,
                        env_var: str = "SPMD_DEVICES",
                        force: bool = False) -> int:
    """Make sure N fake host devices exist; returns the live device count.

    Resolution order for N: explicit ``n`` argument, then ``$SPMD_DEVICES``,
    then ``default``. An existing device-count flag in ``$XLA_FLAGS`` is
    respected unless ``force=True`` (production dry-runs force 512).

    Call this before any other JAX work — if the backend already
    initialized with fewer devices, a RuntimeError explains the fix.
    """
    if n is None:
        env = os.environ.get(env_var)
        n = int(env) if env else int(default)
    n = int(n)
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(re.escape(_FLAG) + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (flags + " " if flags else "") \
            + f"{_FLAG}={n}"
    elif force and int(m.group(1)) != n:
        os.environ["XLA_FLAGS"] = flags.replace(
            m.group(0), f"{_FLAG}={n}")
    else:
        n = int(m.group(1))  # respect the caller's explicit setting

    import jax

    _place_compile_cache()
    devices = jax.devices()
    if devices and devices[0].platform != "cpu":
        # real accelerators: the fake-host-device flag does not apply —
        # run on what the backend provides.
        return len(devices)
    have = len(devices)
    if have < n:
        raise RuntimeError(
            f"requested {n} host devices but JAX already initialized with "
            f"{have}. Call repro.api.ensure_host_devices({n}) (or set "
            f"XLA_FLAGS={_FLAG}={n}) before any other JAX use in this "
            f"process.")
    return have
