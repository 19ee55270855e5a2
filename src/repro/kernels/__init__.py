"""Pallas kernels, and the one place that decides how they run."""
import jax


def interpret_mode() -> bool:
    """Whether a Pallas kernel runs in interpret mode: on the CPU
    backend only.  On an accelerator every kernel compiles."""
    return jax.default_backend() == "cpu"
