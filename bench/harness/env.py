"""The process environment every entry point of the benchmark sets up
before JAX is first imported."""
from __future__ import annotations

import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def prepare() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside
    the checkout (``.jax_cache/``), holding every program however small
    or fast to compile, so that only the first run of a cell in a
    checkout compiles; put the program under test and the benchmark on
    the import path."""
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
