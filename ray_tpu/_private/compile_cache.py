"""Where compiled programs are kept between processes.

JAX's persistent compilation cache is placed from OUTSIDE: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this module
does nothing.  Where it is not, the cache lives at ``<checkout>/.jax_cache`` —
a fixed path, because the path is part of what makes a cache hit, so a
directory named after a pid, a time or a temporary file never hits.

Called by the worker entry (``workers_main``) and by scripts that compile in
their own process; worker processes inherit the variable from whoever
started the node, through the raylet's (and the zygote's rebuilt) environment.
"""

from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
# JAX keeps a program only if it took this long to compile (default 1 s).
# The serving replica's fourteen warm-up programs compile in 0.6 to 2 s
# each, so by the default a warm start found some and compiled the others
# again (4.5 s of a 58 s set-up, PERF.md PR 25): every program is kept.
# An operator's own setting of JAX's variable stands.
_KEEP_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Returns the cache directory in force.  Safe before or after
    ``import jax``; touches no backend."""
    # children (and a jax not imported yet) read the variables ...
    keep = float(os.environ.setdefault(_KEEP_ENV, "0"))
    jax = sys.modules.get("jax")
    if jax is not None:
        # ... a jax already imported read them too early
        jax.config.update("jax_persistent_cache_min_compile_time_secs", keep)
    path = os.environ.get(_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[_ENV] = path
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", path)
    return path
