"""Where JAX's persistent compilation cache lives.

Every run on the chip tool starts on a fresh machine, and the 12-layer
programs take tens of seconds each to compile cold, so compiled programs
are kept on disk. The directory is part of each entry's key: it has to
be the same path in every process or nothing ever hits, so it is never
derived from a temp dir, a pid or the time.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (git-ignored): fixed for a given checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def place_compile_cache():
    """Decide the cache directory and return it (None = no cache). When
    the environment names one (JAX_COMPILATION_CACHE_DIR, which JAX reads
    itself) nothing is set in code — whoever runs the program placed the
    cache. Otherwise, on an accelerator, it goes to DEFAULT_DIR. On the
    CPU backend nothing is placed: CPU compiles are seconds, the tests
    must compile cold whatever an earlier run left behind, and XLA's CPU
    loader logs a machine-feature mismatch for every executable it reads
    back. Idempotent; FFModel.compile() calls it, and scripts that jit
    before their first compile() call it themselves."""
    import jax

    if not os.environ.get(ENV_VAR) and jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return jax.config.jax_compilation_cache_dir
