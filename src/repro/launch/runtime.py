"""Tuned runtime environment — the allocator/flags recipe as code.

The deployment papers attribute a sizeable slice of bridge overhead to the
host runtime rather than the wire: allocator churn on multi-GB staging
buffers and logging noise on the hot path. Production JAX launchers fix this
with a small environment recipe (tcmalloc via ``LD_PRELOAD``, a large-alloc
report threshold so numpy-sized buffers don't warn, quiet TF logging, an
explicit emulated device count, 32-bit default dtypes). This module applies
that recipe reproducibly and — just as important for benchmarking — records
*which* runtime actually ran, so a regression can be attributed to
environment drift instead of code.

``LD_PRELOAD`` only takes effect at process start, so :func:`ensure_tuned`
re-execs the interpreter once with the tuned environment (guarded by a
sentinel variable); ``benchmarks/run.py --tuned`` is the caller.

:func:`enable_compile_cache` places JAX's persistent compilation cache; the
launchers (``chip_smoke.py``, ``benchmarks/run.py``) call it before their
first compile.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import Dict, Optional

#: the repository checkout this module was loaded from (src/repro/launch/..)
CHECKOUT = Path(__file__).resolve().parents[3]

#: sentinel marking "this process was re-exec'd with the tuned env"
_SENTINEL = "REPRO_TUNED"

#: usual tcmalloc install locations (SNIPPETS-style deployments)
_TCMALLOC_PATHS = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
)


def find_tcmalloc() -> Optional[str]:
    """Path of an installed tcmalloc, or None (skip gracefully — CI runners
    without gperftools still run the tuned harness, minus the allocator)."""
    for path in _TCMALLOC_PATHS:
        if os.path.exists(path):
            return path
    return None


def tuned_env(
    base: Optional[Dict[str, str]] = None, device_count: int = 8
) -> Dict[str, str]:
    """The tuned environment: ``base`` (default ``os.environ``) plus the
    recipe. Existing ``XLA_FLAGS`` are merged, not clobbered; an existing
    ``LD_PRELOAD`` is left alone (the operator knows better)."""
    env = dict(base if base is not None else os.environ)
    env[_SENTINEL] = "1"
    tcmalloc = find_tcmalloc()
    if tcmalloc and "LD_PRELOAD" not in env:
        env["LD_PRELOAD"] = tcmalloc
    # no large-alloc warnings on multi-GB staging buffers
    env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000")
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")  # quiet the hot path
    env.setdefault("JAX_DEFAULT_DTYPE_BITS", "32")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = f"{flags} --xla_force_host_platform_device_count={device_count}".strip()
        env["XLA_FLAGS"] = flags
    return env


def is_tuned() -> bool:
    """Is this process running under the tuned environment?"""
    return os.environ.get(_SENTINEL) == "1"


def ensure_tuned(device_count: int = 8) -> None:
    """Re-exec the interpreter once with the tuned environment.

    No-op when already tuned. Must run before ``import jax`` to matter:
    ``LD_PRELOAD`` and ``XLA_FLAGS`` bind at process/backend start.
    """
    if is_tuned():
        return
    env = tuned_env(device_count=device_count)
    # ``python -m pkg.mod`` resolves against the CWD, but the re-exec sees
    # argv[0] as the resolved script path and runs in script mode — keep the
    # launch directory importable so ``import benchmarks`` still works.
    cwd = os.getcwd()
    pythonpath = env.get("PYTHONPATH", "")
    if cwd not in pythonpath.split(os.pathsep):
        env["PYTHONPATH"] = f"{cwd}{os.pathsep}{pythonpath}" if pythonpath else cwd
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def enable_compile_cache() -> str:
    """Put JAX's persistent compilation cache in a fixed place; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself, so
    nothing is set in code. Otherwise the cache is ``<checkout>/.jax_cache``:
    a path that never moves between runs, since the path is part of what a
    cached entry is found by. Call before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _loaded_allocator() -> str:
    """Which malloc actually got loaded (parsed from /proc/self/maps) —
    records the truth, not the intent: a bad LD_PRELOAD silently falls back
    to glibc and would otherwise masquerade as tuned."""
    try:
        with open("/proc/self/maps") as f:
            maps = f.read()
    except OSError:  # pragma: no cover - non-Linux
        return "unknown"
    if "tcmalloc" in maps:
        return "tcmalloc"
    if "jemalloc" in maps:
        return "jemalloc"
    return "glibc"


def snapshot() -> Dict[str, object]:
    """JSON-serializable record of the runtime this process actually has.

    Embedded in every benchmark suite's metrics block so regressions are
    attributable to environment drift (allocator, device count, flags).
    """
    import jax

    return {
        "tuned": is_tuned(),
        "allocator": _loaded_allocator(),
        "ld_preload": os.environ.get("LD_PRELOAD", ""),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "default_dtype_bits": os.environ.get("JAX_DEFAULT_DTYPE_BITS", ""),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": sys.version.split()[0],
    }
