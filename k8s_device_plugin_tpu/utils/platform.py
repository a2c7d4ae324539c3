"""What every JAX entry point shares about the device it runs on: the
facts it prints (platform, device_kind, count), the per-chip peak MFU is
taken against, the one persistent compile-cache rule, and the argparse
type their command lines share.

Platform selection itself is JAX's own: ``JAX_PLATFORMS`` in the pod
spec (or the smoke's child environment) decides, and a listed platform
that cannot initialise is an error, never a quiet CPU run.
"""

from __future__ import annotations

import argparse
import os
from typing import Callable, Optional


# Peak dense bf16 matmul throughput per chip, by device_kind substring
# (first match wins; more specific substrings first).  Public figures:
# v4 275, v5e 197, v5p 459, v6e/Trillium 918, v3 123, v2 45 TFLOP/s.
# The v5e reports itself as "TPU v5 lite" (chip run, PR 21).
PEAK_BF16_FLOPS_BY_KIND: tuple[tuple[str, float], ...] = (
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v6", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# <checkout>/.jax_cache, from this file's own location
# (k8s_device_plugin_tpu/utils/platform.py): the path is part of what a
# warm start depends on, so it never comes from /tmp, a pid or the clock.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def peak_bf16_flops(device) -> float:
    """Per-chip peak bf16 FLOP/s for a jax device.  A device_kind the
    table does not know raises: an MFU against a guessed peak is worse
    than no MFU, and a silently missing one hides that the table is
    stale."""
    kind = getattr(device, "device_kind", "") or ""
    lowered = kind.lower()
    for sub, peak in PEAK_BF16_FLOPS_BY_KIND:
        if sub in lowered:
            return peak
    raise ValueError(
        f"no bf16 peak for device_kind {kind!r}: add a row to "
        "PEAK_BF16_FLOPS_BY_KIND (utils/platform.py) before reporting MFU"
    )


def device_facts() -> dict:
    """The three facts every record and debug surface names, as JAX
    reports them: ``jax.devices()[0].platform``, ``.device_kind`` and
    ``len(jax.devices())``."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def enable_compilation_cache(
    *,
    min_compile_seconds: float = 1.0,
    log: Optional[Callable[[str], None]] = None,
) -> str:
    """The one persistent compile-cache rule (serving server, engine CLI,
    benchmark runner).  Where ``JAX_COMPILATION_CACHE_DIR`` is
    set — the deploy manifests point it at their mounted emptyDir — JAX
    reads it itself and this sets no directory in code; otherwise the
    cache lives at ``<checkout>/.jax_cache``.  Returns the directory in
    use.

    ``min_compile_seconds`` filters entries: only compilations at least
    this slow are written (sub-second CPU test compiles would churn the
    directory).
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILATION_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_seconds
    )
    if log is not None:
        log(f"persistent compilation cache at {cache_dir}")
    return cache_dir


def positive_int(text: str) -> int:
    """argparse type: an integer >= 1."""
    value = int(text)
    if value < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {value}")
    return value
