"""What the process runs on, and where it keeps its compiled programs.

Stock JAX honours ``JAX_PLATFORMS``, so nothing here selects a platform.
JAX also falls back to the CPU WITHOUT an error when libtpu is installed
but finds no chip: a caller whose output is a device number must ask
``device_info(need_tpu=True)`` first, or a CPU figure gets a TPU's name.
"""

from __future__ import annotations

import os
import re
from typing import Optional

REPO_DIR = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def device_info(need_tpu: bool = False) -> dict:
    """``{"platform", "kind", "count"}`` of this process's default backend,
    as JAX reports it. With ``need_tpu`` a backend that is not a TPU raises
    instead of being described."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if need_tpu and info["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: JAX offers {info['count']} {info['platform']} "
            f"device(s) ({info['kind']}; JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}), and this path "
            "produces device numbers"
        )
    return info


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.
    Call before the first compile; every entry point does.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it and nothing
    is touched. Unset: ``<checkout>/.xla_compile_cache``. The directory is
    part of every cache key, so it is a fixed path and never one built
    from a temp dir, a pid or the time — a cache that moves never hits."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(REPO_DIR, ".xla_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_cpu() -> None:
    """Pin this process to the CPU platform after jax was imported (tests
    and the multi-device dry run; ``JAX_PLATFORMS=cpu`` in the environment
    does the same from outside)."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def cpu_mesh_env(n_devices: int, base: Optional[dict] = None) -> dict:
    """Env for a subprocess that must see ``n_devices`` virtual CPU devices.

    XLA_FLAGS must precede backend init, hence a fresh env rather than
    in-process mutation.
    """
    env = dict(base if base is not None else os.environ)
    flags = env.get("XLA_FLAGS", "")
    # Replace any existing count with max(existing, n_devices) — keeping a
    # smaller leftover count would hand the child too few devices.
    pat = r"--xla_force_host_platform_device_count=(\d+)"
    m = re.search(pat, flags)
    if m:
        count = max(int(m.group(1)), n_devices)
        flags = re.sub(pat, f"--xla_force_host_platform_device_count={count}", flags)
    else:
        flags = (flags + f" --xla_force_host_platform_device_count={n_devices}").strip()
    env["XLA_FLAGS"] = flags
    env["JAX_PLATFORMS"] = "cpu"
    return env


def setup_platform() -> None:
    setup_compile_cache()
    from keystone_tpu.config import config, env_flag

    if config.debug_nans or env_flag("KEYSTONE_DEBUG_NANS"):
        import jax

        jax.config.update("jax_debug_nans", True)
    if env_flag("KEYSTONE_AUTO_CACHE"):
        config.auto_cache = True
    # Multi-host rendezvous when the env knobs are present (no-op otherwise).
    from keystone_tpu.utils import distributed

    distributed.initialize()
