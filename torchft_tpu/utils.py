"""Accelerator/platform helpers (reference: torchft utils.py:17-67).

The reference's utils provide stream-context and event helpers for
cuda/xpu; on TPU, JAX's async dispatch replaces user-managed streams, so the
helpers here cover the platform concerns this framework actually has:
forcing a virtual multi-device CPU platform for tests and dry runs, and
blocking on device work.
"""

from __future__ import annotations

import os
import re
from typing import Any

_FLAG = "xla_force_host_platform_device_count"


def probe_backend(timeout_s: float = 60.0) -> "tuple[str, str]":
    """Probe the default JAX backend in a SUBPROCESS; (status, detail).

    status: "accel" (an accelerator initializes), "cpu" (init works, CPU
    only), "crash" (init fails), "hung" (init did not return in
    ``timeout_s``). For parents that must stay off JAX because their
    children need the chip (a chip belongs to one process at a time):
    ``benchmarks/mfu_sweep.py`` and ``python -m torchft_tpu.doctor``.
    """
    import subprocess
    import sys

    try:
        out = subprocess.run(
            [sys.executable, "-c",
             "import jax; d = jax.devices(); "
             "print('PROBE', jax.default_backend(), len(d))"],
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return "hung", f"backend init hung >{timeout_s:.0f}s"
    if out.returncode != 0:
        return "crash", out.stderr.strip()[-300:]
    # scan for the sentinel line: runtimes love writing log lines to stdout
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("PROBE "):
            _, backend, n = line.split()
            status = "cpu" if backend == "cpu" else "accel"
            return status, f"{backend} ({n} device(s))"
    return "crash", f"probe printed no result: {out.stdout[-200:]!r}"


def force_virtual_cpu_devices(n: int) -> None:
    """Force a virtual ``n``-device CPU platform (tests and dry runs).

    Must run before the first JAX backend initialisation (importing jax is
    fine — ``XLA_FLAGS`` is read at backend-init time). Overrides any
    pre-existing smaller device-count flag, and flips ``jax_platforms`` to
    cpu so an accelerator on the host is left alone.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG in flags:
        def _bump(m: "re.Match[str]") -> str:
            return f"--{_FLAG}={max(n, int(m.group(1)))}"

        flags = re.sub(rf"--{_FLAG}=(\d+)", _bump, flags)
    else:
        flags = f"{flags} --{_FLAG}={n}".strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass  # backend already initialised; caller's device check reports it


CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def compilation_cache_dir() -> str:
    """The persistent compile cache directory, exported for children.

    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
    that variable itself; nothing else is set in code), otherwise the fixed
    ``<checkout>/.jax_cache``. The path is part of the cache key's
    provenance, so it never carries a pid, a time or a temporary name.
    Touches no JAX state: a parent that must stay off the chip (the
    launcher, ``chip_smoke.py``) calls this and its children inherit the
    variable.
    """
    cache_dir = os.environ.get(CACHE_DIR_ENV)
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        os.environ[CACHE_DIR_ENV] = cache_dir
    os.makedirs(cache_dir, exist_ok=True)
    return cache_dir


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache at
    ``compilation_cache_dir()`` for this process; returns the directory.

    Call before the first compile. Every executable is cached (no minimum
    compile time or size): the bench_1b train step compiles in ~35 s on a
    v5e and loads in a few (chip run, PR 21).
    """
    cache_dir = compilation_cache_dir()

    import jax

    if jax.config.jax_compilation_cache_dir != cache_dir:
        # only where jax was imported before the variable was exported;
        # where the environment set it, jax already has it and code sets
        # no directory
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def np_dtype_from_str(name: str):
    """np.dtype for a dtype name, including ml_dtypes extended types
    (bfloat16, float8_*) that plain np.dtype() doesn't know."""
    import numpy as np

    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


# Dense bf16 peak FLOP/s per chip, keyed by ``jax.Device.device_kind``
# exactly as the runtime reports it (strings as in jax's own
# pallas/mosaic/tpu_info.py; peaks from the Google Cloud TPU system
# documentation). "TPU v5 lite" is the one kind seen on a chip (PR 21).
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # v6e
}


def peak_flops_per_chip(device_kind: "str | None" = None) -> float:
    """Dense bf16 peak FLOP/s of ``device_kind`` (default: the local
    device's): the MFU denominator. A kind that is not in
    ``PEAK_BF16_FLOPS`` is an error, never a default — a utilization
    against a made-up peak is not a measurement."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {device_kind!r}; known: "
            f"{sorted(PEAK_BF16_FLOPS)} (add a sourced row to "
            "torchft_tpu.utils.PEAK_BF16_FLOPS)"
        ) from None


def synchronize(tree: Any) -> Any:
    """Block until every array in ``tree`` has been computed.

    The analog of the reference's ``utils.synchronize`` (utils.py:58-67):
    JAX dispatch is async, so callers that need a host-visible completion
    point (commit gates, timing) block on the arrays themselves.
    """
    import jax

    return jax.block_until_ready(tree)
