"""Shared helpers for the repo-root entry points (``chip_smoke.py``,
``__graft_entry__.py``), the tools and the tests: CPU
pinning for child processes and the one compile-cache placement rule.

No jax and no deepspeed_tpu import at module level, so parent processes
can orchestrate without touching any accelerator backend.
"""

import os

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))


def to_text(maybe_bytes) -> str:
    """Normalize subprocess.TimeoutExpired stdout/stderr (bytes | str | None)."""
    if isinstance(maybe_bytes, bytes):
        return maybe_bytes.decode(errors="replace")
    return maybe_bytes or ""


def cpu_subprocess_env(n_virtual_devices: int = 0) -> dict:
    """A copy of os.environ pinned to the CPU platform; optionally forcing
    ``n_virtual_devices`` XLA host devices (0 = leave XLA_FLAGS alone)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    if n_virtual_devices:
        flags = env.get("XLA_FLAGS", "")
        flags = " ".join(f for f in flags.split()
                         if "xla_force_host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_virtual_devices}").strip()
    return env


def pin_cpu_in_process(n_virtual_devices: int = 8) -> None:
    """Pin THIS process to the CPU platform before jax is imported (example
    scripts' --cpu mode), forcing ``n_virtual_devices`` XLA host devices."""
    os.environ.update(cpu_subprocess_env(n_virtual_devices))


def use_compile_cache(min_compile_secs: float = 1.0) -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside: when it is set,
    jax already reads it and no directory is set in code. Otherwise the
    cache is ``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of the cache key. Programs that compile faster than
    ``min_compile_secs`` are not cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_compile_secs)
    return path
