"""Process set-up shared by the entry points: ``--devices`` and the compile
cache.

``--devices N`` asks for a flow mesh over the first N devices of the
platform JAX runs on.  On the CPU (``JAX_PLATFORMS=cpu``) those are host
devices, which XLA creates from ``--xla_force_host_platform_device_count``
when the CPU backend initializes, so `request_devices` must run BEFORE
anything initializes JAX: this module imports no JAX at the top, and the
entry points defer their JAX-touching imports until after it has run.  On
an accelerator the devices are the chips present; nothing is forced, and
asking for more than there are is an error (`repro.net.sender.flow_mesh`).

`setup_compile_cache` keeps JAX's persistent compilation cache at a fixed
path, so a second run of the same program loads its executables instead of
compiling them again.
"""
from __future__ import annotations

import argparse
import os
import sys

__all__ = [
    "CACHE_DIR",
    "add_devices_arg",
    "request_devices",
    "setup_compile_cache",
]

_FLAG = "--xla_force_host_platform_device_count"

# fixed, inside the checkout (listed in .gitignore): the cache directory is
# part of what a later run must find again, so it never depends on a
# temporary name, a process id or the time
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))),
    ".jax_cache",
)


def add_devices_arg(ap: argparse.ArgumentParser) -> None:
    ap.add_argument(
        "--devices", type=int, default=None, metavar="N",
        help="run the sweep through the flow-sharded engine on a flow mesh "
        "over the first N devices of the platform JAX runs on (bit-identical "
        "results).  On the CPU (JAX_PLATFORMS=cpu) N host devices are "
        f"forced ({_FLAG}=N) before JAX initializes; on an accelerator N "
        "may not exceed the devices present",
    )


def _cpu_platform() -> bool:
    """True when ``JAX_PLATFORMS`` makes the CPU the platform JAX runs on."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def request_devices(n: int) -> None:
    """Arrange for `n` devices before JAX initializes.

    On the CPU this exports the forced-host-device flag, failing LOUDLY if
    JAX has already initialized with fewer devices.  Elsewhere it sets
    nothing: the mesh is built over the devices present, and `flow_mesh`
    refuses a request for more.
    """
    if n < 1:
        raise SystemExit(f"--devices {n}: need >= 1")
    if not _cpu_platform():
        return
    flag = f"{_FLAG}={n}"
    if "jax" in sys.modules:
        import jax

        if jax.device_count() < n:
            raise SystemExit(
                f"--devices {n}: jax already initialized with "
                f"{jax.device_count()} device(s); XLA_FLAGS must be set "
                f"before the first jax import — export XLA_FLAGS='{flag}' "
                "in the shell or make this CLI the process entry point"
            )
        return
    kept = [
        p for p in os.environ.get("XLA_FLAGS", "").split()
        if not p.startswith(_FLAG)
    ]
    os.environ["XLA_FLAGS"] = " ".join(kept + [flag])


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it as the cache
    directory and nothing is set here.  Otherwise the cache lives at
    `CACHE_DIR`, inside the checkout.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
