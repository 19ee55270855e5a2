"""Per-vertex feature precompute and padding for the hypdist kernel."""
from __future__ import annotations

import math

import numpy as np

FEAT = 8  # 4 features padded to sublane width

# cosh overflows float64 just past this point (cosh(x) ~ e^x / 2)
_COSH_OVERFLOW_R = 700.0


def cosh_threshold(R: float) -> float:
    """cosh(R) for the Eq. 9 threshold, overflow-free.

    Above the float64 overflow point the comparison is evaluated in the
    log domain (log cosh R = R - log 2 + log1p(e^-2R)) and clamped to
    the largest finite float64 — every real feature product still
    compares on the correct side, and no RuntimeWarning is emitted.
    """
    R = abs(float(R))
    if R < _COSH_OVERFLOW_R:
        return math.cosh(R)
    log_cosh = R - math.log(2.0) + math.log1p(math.exp(-2.0 * R))
    if log_cosh >= math.log(np.finfo(np.float64).max):
        return float(np.finfo(np.float64).max)
    return math.exp(log_cosh)

# padding rows: coth = +huge makes the Eq. 9 expression strongly negative
_PAD_ROW = np.array([0.0, 0.0, 1e30, 0.0, 0, 0, 0, 0])


def precompute_features(r: np.ndarray, theta: np.ndarray, dtype=np.float64) -> np.ndarray:
    """(N, 8): [cos θ, sin θ, coth r, 1/sinh r, 0...] (paper §7.2.1)."""
    r = np.maximum(np.asarray(r, np.float64), 1e-12)
    sh = np.sinh(r)
    out = np.zeros((len(r), FEAT), np.float64)
    out[:, 0] = np.cos(theta)
    out[:, 1] = np.sin(theta)
    out[:, 2] = np.cosh(r) / sh
    out[:, 3] = 1.0 / sh
    return out.astype(dtype)


def pad_features(feat: np.ndarray, rows: int | None = None, dtype=np.float64) -> np.ndarray:
    n = len(feat)
    rows = rows if rows is not None else (n + 127) // 128 * 128
    rows = max(128, (rows + 127) // 128 * 128)
    out = np.tile(_PAD_ROW, (rows, 1))
    out[:n] = feat
    return out.astype(dtype)
