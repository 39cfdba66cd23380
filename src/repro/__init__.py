"""AFrame on JAX: a pandas-style DataFrame front end over a managed,
device-resident relational engine.

Importing the package places JAX's persistent compilation cache, the one
place in the program that does: where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX keeps the cache there and nothing here overrides it; otherwise the
cache lives at ``<checkout>/.jax_cache``, a fixed path, so a later process
in the same checkout finds what an earlier one compiled.
"""
import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
