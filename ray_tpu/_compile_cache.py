"""Where this process tree keeps XLA's persistent compile cache.

The cache directory is part of what a hit needs: a path that moves never
hits. The rule, decided here and nowhere else:

- `JAX_COMPILATION_CACHE_DIR` set from outside: jax reads it itself at
  import; this module sets no directory.
- unset: one fixed path inside the checkout, `<checkout>/.jax_cache` —
  never under tempfile, a pid, a timestamp or the runtime's session
  directory. It is exported, so head, nodelet, train workers and serve
  replicas spawned from here resolve the same directory.

`configure()` runs when `ray_tpu` is imported, which every process of the
tree does before its first compile.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure() -> str:
    """Apply the rule; returns the directory in use."""
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[ENV] = path
    jax = sys.modules.get("jax")
    if jax is not None:
        # jax read the (then unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", path)
    return path
