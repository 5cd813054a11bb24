"""Where XLA's persistent compilation cache lives — decided once, here.

Every process of this framework starts cold on the chip (a benchmark run, a
serving replica, a worker of a launched job), and one ResNet-50 training
step is over a minute of compilation on a TPU v5e. So the cache is always
on, and its location can be chosen from outside the program:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing here
  touches the setting.
- unset: a fixed directory in the checkout (``<repo>/.jax_cache``,
  git-ignored). Fixed on purpose — a path with a pid, a time or a temp name
  in it is never found again by the next process.

No other module sets ``jax_compilation_cache_dir``.

The serving caches' exported programs (serving/cache.py: the program store)
live in a directory of that one, ``program_store()``, so whoever moves, empties
or turns off the compile cache does the same to them.
"""
import os

__all__ = ["DEFAULT_DIR", "configure", "directory", "program_store"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def configure():
    """Called once at package import."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)


def directory():
    """The cache directory in effect."""
    import jax

    return jax.config.jax_compilation_cache_dir


def program_store():
    """Where exported programs are kept, or None where nothing is: the store
    is on exactly where the compile cache is (a process that holds itself out
    of the one, as the tests and a rehearsal do, is out of the other)."""
    import jax

    root = directory()
    if not root or not jax.config.jax_enable_compilation_cache:
        return None
    return os.path.join(root, "mx_programs")
