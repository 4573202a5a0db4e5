"""Shared host-side utilities."""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compilation_cache() -> None:
    """Turn on JAX's persistent executable cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads the directory
    from it and this function sets none in code; otherwise the cache lives
    in ``<checkout>/.jax_cache``. The path is part of the cache key, so it
    has to be the same from one run to the next. Every process that holds
    the device calls this before its first jit: the resolver role at boot,
    bench.py, chip_smoke.py's children and the test suite."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_REPO_ROOT, ".jax_cache"))
    # On a device every compiled program is kept, so a second run of the
    # same command compiles nothing and adds nothing. Where the CPU backend
    # was asked for (the tests), XLA compiles thousands of small programs
    # in well under a second each, and those are not worth a file apiece.
    cpu_pinned = os.environ.get("JAX_PLATFORMS") == "cpu"
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      1.0 if cpu_pinned else 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def describe_devices(devices) -> dict:
    """A set of JAX devices as every record names them."""
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices)}


def device_summary() -> dict:
    """The devices JAX gave this process."""
    import jax

    return describe_devices(jax.devices())


def require_tpu(what: str) -> dict:
    """``device_summary()``, or RuntimeError when it is not a TPU.

    JAX falls back to the CPU without raising when libtpu cannot reach a
    chip, so whatever was asked to run on the TPU checks here before it
    builds anything. ``JAX_PLATFORMS=cpu`` in the environment is the one
    way to run such a path on the CPU backend, as the tests do."""
    info = device_summary()
    if info["platform"] != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"{what} needs a TPU but JAX found platform "
            f"{info['platform']!r} ({info['device_kind']}, "
            f"{info['count']} device(s)); set JAX_PLATFORMS=cpu to run it "
            "on the CPU backend on purpose")
    return info
