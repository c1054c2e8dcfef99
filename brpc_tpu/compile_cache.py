"""JAX's persistent compilation cache, configured in one place.

chip_smoke.py, benchmark/run.py and __graft_entry__.py call enable()
before they compile. Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it
itself and no other directory is set. Otherwise the cache lives at a fixed path in
the checkout: a path derived from a tempdir, a pid or the time would be
new on every run and never hit.
"""
import os

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable() -> str:
    """Point JAX's compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
