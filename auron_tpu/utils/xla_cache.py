"""Where jax's persistent compilation cache lives — decided in ONE place.

Every program this engine runs is an XLA build, and a TPU build costs
from tenths of a second to minutes, so the cache is always on. The
directory is, in order:

1. ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it — jax reads
   that variable itself, so nothing here (or anywhere in the repo)
   touches ``jax_compilation_cache_dir``: whoever runs the process owns
   the placement;
2. ``auron.xla_cache_dir`` when a deployment names one;
3. ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because a
   directory that moves between runs never hits.

jax's floor on the compile time worth persisting (1 s by default) is
lowered to zero unless the environment sets it: most of this engine's
programs compile in well under a second, and a query suite builds
hundreds of them.
"""

from __future__ import annotations

import os

_ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the directory holding the ``auron_tpu`` package
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir(conf=None) -> str:
    """The directory the persistent compilation cache uses."""
    env = os.environ.get(_ENV_DIR)
    if env:
        return env
    from auron_tpu import config as cfg
    if conf is None:
        conf = cfg.get_config()
    return conf.get(cfg.XLA_CACHE_DIR) or os.path.join(_CHECKOUT,
                                                       ".jax_cache")


def bind(conf=None) -> str:
    """Point jax at :func:`cache_dir` and return it. Idempotent; called
    from Session init and from entry points that compile before any
    Session exists."""
    import jax
    path = cache_dir(conf)
    if not os.environ.get(_ENV_DIR) \
            and jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ \
            and jax.config.jax_persistent_cache_min_compile_time_secs != 0:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    return path


def entries(path: str) -> int:
    """Cached executables under ``path`` (0 for a directory that does
    not exist yet)."""
    try:
        return sum(1 for name in os.listdir(path)
                   if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
