"""Process set-up for the entry points: JAX's compile cache, and the check
that a process which should hold the TPU really does.

Entry points call these (``chip_smoke.py``, the sweep worker, the
service's ``--run`` child, ``benchmarks/run.py``); nothing here runs at
import. A TPU chip belongs to one process at a time: a second process on
the host cannot open it, and JAX then only warns and carries on on the
CPU. ``require_expected_platform`` turns that into an error, and the sweep
launcher keeps its own process off the JAX backend altogether.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CACHE_ENV", "EXIT_NO_DEVICE", "REPO_CACHE_DIR",
           "init_compile_cache", "require_expected_platform",
           "tpu_chips_on_host", "tpu_expected"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed path inside the checkout (git-ignored): the cache path is part of
# the cache key, so a directory that moved between runs would never hit.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
# Exit code of a worker that found no TPU where the host has one; the
# launcher stops on it instead of retrying.
EXIT_NO_DEVICE = 3


def init_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and no other directory is set here; otherwise the cache goes to
    ``<repo>/.jax_cache``."""
    import jax

    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def tpu_chips_on_host() -> int:
    """TPU chips attached to this host over PCI, counted without opening
    a JAX backend (safe in a supervisor that must stay off the chip)."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def tpu_expected() -> bool:
    """True when the host has TPU chips and ``JAX_PLATFORMS`` does not
    steer JAX away from them."""
    wanted = os.environ.get("JAX_PLATFORMS", "")
    return (not wanted or "tpu" in wanted) and tpu_chips_on_host() > 0


def require_expected_platform() -> str:
    """Return the default backend's platform; raise when a TPU was
    expected and JAX came up on another one."""
    import jax
    from jax._src import xla_bridge

    platform = jax.default_backend()
    if platform != "tpu" and tpu_expected():
        why = xla_bridge._backend_errors.get("tpu", "no TPU backend")
        raise RuntimeError(
            f"this host has {tpu_chips_on_host()} TPU chip(s) but JAX came "
            f"up on '{platform}' ({why}); another process probably holds "
            f"the chip — refusing to run on the CPU")
    return platform
