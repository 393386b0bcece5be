"""Where this process tree keeps XLA's persistent compile cache, and what
it keeps there.

The cache directory is part of what a hit needs: a path that moves never
hits. The rule, decided here and nowhere else:

- `JAX_COMPILATION_CACHE_DIR` set from outside: jax reads it itself at
  import; this module sets no directory.
- unset: one fixed path inside the checkout, `<checkout>/.jax_cache` —
  never under tempfile, a pid, a timestamp or the runtime's session
  directory. It is exported, so head, nodelet, train workers and serve
  replicas spawned from here resolve the same directory.

The floor under which jax does not write a program it compiled
(`jax_persistent_cache_min_compile_time_secs`, 1 s as jax comes) follows
the same rule:

- `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS` set from outside: left
  alone.
- unset, in a process pinned to the CPU (`JAX_PLATFORMS` names only
  `cpu`): jax's own floor stays. The CPU compiles nearly everything in
  under a second, and a test run would write some ten thousand files into
  the checkout.
- unset otherwise: 0, exported. A start on a chip runs a few dozen
  programs that compile in about a second, some of them over the floor on
  one start and under it on the next: with the floor at 0 every program a
  start compiled is found by the next one.

`configure()` runs when `ray_tpu` is imported, which every process of the
tree does before its first compile.
"""

from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
FLOOR_ENV = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _pinned_to_cpu(jax) -> bool:
    # jax read the variable when it was imported, and a later
    # `jax.config.update("jax_platforms", ...)` (tests/conftest.py) wins
    platforms = (jax.config.jax_platforms if jax is not None
                 else os.environ.get("JAX_PLATFORMS")) or ""
    names = {p.strip() for p in platforms.split(",") if p.strip()}
    return names == {"cpu"}


def configure() -> str:
    """Apply the rule; returns the directory in use."""
    jax = sys.modules.get("jax")
    if not os.environ.get(FLOOR_ENV) and not _pinned_to_cpu(jax):
        os.environ[FLOOR_ENV] = "0"
        if jax is not None:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get(ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[ENV] = path
    if jax is not None:
        # jax read the (then unset) variable when it was imported
        jax.config.update("jax_compilation_cache_dir", path)
    return path
