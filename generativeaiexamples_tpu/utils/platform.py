"""Process-level JAX set-up shared by every entry point that compiles."""

from __future__ import annotations

import functools
import logging
import os

_LOG = logging.getLogger(__name__)

# One fixed place inside the checkout: the cache key covers the path, so
# a directory that moves (a temp dir, a pid, a date) never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache (a cold llama3-8b boot
    compiles for minutes; a warm one reads the programs back) and return
    its directory. Called once by each entry point that compiles — the
    engine server, a chain server with in-process engines,
    benchmark/run.py, chip_smoke.py's children — before the first compile.

    Where `JAX_COMPILATION_CACHE_DIR` is set the directory is the
    operator's: JAX reads the variable itself and no directory is set
    here. Otherwise it is `<checkout>/.jax_cache`. Failure to create it
    raises: a boot that silently recompiles everything is the fault
    this exists to prevent."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache everything that took meaningful compile time; the decode
    # graph is the one that matters and compiles in seconds.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


@functools.lru_cache(maxsize=None)
def log_kernel_declined(site: str, route: str, why: str) -> None:
    """A gate kept `site`'s Pallas kernel off although Pallas was
    selected (the backend is the TPU): say which route it took instead
    and why, ONCE per distinct message, at trace time. The slower route
    is correct, so nothing else would ever show it. chip_smoke.py fails
    a served profile whose engine log carries this line."""
    _LOG.warning("kernel declined: %s takes %s (%s)", site, route, why)
