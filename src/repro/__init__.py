"""KaGen-JAX: communication-free massively distributed graph generation,
plus the multi-pod training/serving framework it feeds.

x64 is enabled globally: edge universes exceed 2^32 almost immediately
(n(n-1)/2 for n = 2^17 already does).  All model code uses explicit
dtypes, so LM compute stays bf16/f32 regardless.

JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says; without it, in ``.jax_cache`` at
the root of the checkout (a fixed path, so later processes hit it).
"""
import os
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir",
                      str(Path(__file__).resolve().parents[2] / ".jax_cache"))

__version__ = "1.0.0"
