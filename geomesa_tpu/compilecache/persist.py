"""Library-level persistent XLA compilation cache.

A cold Mosaic kernel compile costs seconds to a minute, so every process
restart that re-pays it shows up as cold-start latency.
`enable_persistent_cache()` is the one shared entry point for the
planner, `QueryService`, `gmtpu serve` and bench: idempotent, never
raises, safe to call from library constructors.

Where the cache lives, first match wins:

1. an explicit `cache_dir` argument, or the `geomesa.compile.cache.dir`
   system property (env `GEOMESA_TPU_COMPILE_CACHE_DIR`); `off` (or `0`,
   `false`, `none`) disables the cache entirely;
2. `JAX_COMPILATION_CACHE_DIR`, when the environment sets it: JAX reads
   it itself, so the library leaves `jax_compilation_cache_dir` alone
   and adds no subdirectory — whoever placed the cache keeps it;
3. `<checkout>/.jax_cache/<backend>`, a fixed path (the path is part of
   what makes a later process find the entries) that `.gitignore`
   lists; for an installed package (no `pyproject.toml` above it),
   `$XDG_CACHE_HOME` or `~/.cache`, then `geomesa_tpu/jax_cache/<backend>`.
   The per-backend subdirectory keeps CPU and TPU executables apart.

A failed enable leaves serving uncached but not silent: it logs a
warning and counts `compilecache.persistent.enable_failed`.

A Pallas kernel carries its source locations, file paths included, into
the compiled program and so into the cache key, which would make every
Mosaic program miss from another checkout directory. Enabling the cache
therefore strips the checkout prefix from source paths, unless the
caller already set `jax_hlo_source_file_canonicalization_regex`.
"""

from __future__ import annotations

import logging
import os
import re
import threading
from typing import Optional

from geomesa_tpu.faults import harness as _faults

_lock = threading.Lock()
_enabled_dir: Optional[str] = None

DISABLE_TOKENS = ("off", "0", "false", "none")

# compile-cache boundary site: an injected failure here exercises the
# documented degrade path (the cache is an optimization, never a
# failure — enable returns None and serving continues uncached)
_PERSIST_SITE = _faults.site(
    "compilecache.persist", "persistent XLA cache dir setup/config")


_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
JAX_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """The base directory when nothing is configured: fixed, inside a
    source checkout; the per-user cache directory for an installed
    package, whose parent directory is site-packages."""
    if os.path.exists(os.path.join(_CHECKOUT, "pyproject.toml")):
        return os.path.join(_CHECKOUT, ".jax_cache")
    user = os.environ.get("XDG_CACHE_HOME") or os.path.join(
        os.path.expanduser("~"), ".cache")
    return os.path.join(user, "geomesa_tpu", "jax_cache")


def enable_persistent_cache(
    cache_dir: Optional[str] = None,
    min_entry_bytes: int = -1,
    min_compile_secs: float = 0.0,
    per_platform: bool = True,
    force: bool = False,
) -> Optional[str]:
    """Point jax's persistent compilation cache at `cache_dir` (default:
    resolved as the module docstring says). Returns the directory in
    effect, or None when disabled/unavailable. Idempotent: after the
    first successful call, later calls are no-ops unless `force=True`
    (so the planner, the serving layer and bench can all call it
    unconditionally and the first caller wins).

    `min_entry_bytes=-1` / `min_compile_secs=0.0` persist EVERY
    executable — the serving cold-start contract wants the whole warmup
    manifest to hit disk, not just the multi-second Mosaic kernels.
    The cache is an optimization, never a failure: every error path
    degrades to "no cache".
    """
    global _enabled_dir
    with _lock:
        if _enabled_dir is not None and not force:
            return _enabled_dir
        from geomesa_tpu.utils.config import SystemProperties

        base = cache_dir or str(SystemProperties.COMPILE_CACHE_DIR.get()
                                or "")
        if base.lower() in DISABLE_TOKENS:
            return None
        from_env = not base and bool(os.environ.get(JAX_ENV))
        try:
            _PERSIST_SITE.fire()
            import jax

            # gt: waive GT25
            # (the branch only decides where executables persist; no
            # arm changes what any process compiles or dispatches)
            if from_env:
                path = os.environ[JAX_ENV]
            else:
                path = base or default_cache_dir()
                if per_platform:
                    # default_backend() initializes the backend; callers
                    # of this helper are about to compile anyway
                    path = os.path.join(path, jax.default_backend())
                os.makedirs(path, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", path)
            if not jax.config.jax_hlo_source_file_canonicalization_regex:
                jax.config.update(
                    "jax_hlo_source_file_canonicalization_regex",
                    "^" + re.escape(_CHECKOUT + os.sep))
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes",
                int(min_entry_bytes))
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs",
                float(min_compile_secs))
            _enabled_dir = path
            from geomesa_tpu.utils.metrics import metrics

            metrics.gauge("compilecache.persistent.enabled", 1.0)
            return path
        except Exception as e:  # noqa: BLE001 — degrade, but visibly
            from geomesa_tpu.utils.metrics import metrics

            metrics.counter("compilecache.persistent.enable_failed")
            logging.getLogger(__name__).warning(
                "persistent compile cache off: %s: %s",
                type(e).__name__, e)
            return None


def persistent_cache_dir() -> Optional[str]:
    """The directory a prior `enable_persistent_cache()` call put in
    effect this process, or None."""
    with _lock:
        return _enabled_dir


def disable_persistent_cache() -> None:
    """Detach jax from the persistent cache directory and forget the
    enabled state (so a later enable_persistent_cache() re-resolves).
    Used by the chaos runner to restore a pristine state after pointing
    the cache at a throwaway directory; same never-fails contract as
    enable."""
    global _enabled_dir
    with _lock:
        try:
            import jax

            jax.config.update("jax_compilation_cache_dir", None)
        except Exception:
            pass
        _enabled_dir = None
