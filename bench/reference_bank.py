"""Plain reference of one query against a template bank, for the check of
the bank cell.

Each template's correlation grid C(R) = <f, Lambda(R) g> comes from the f64
reference SO(3) FFT (``bench.reference.correlation``); its peak is numpy's
argmax of Re C, and the rotation is refined by a three-point quadratic per
Euler axis through the peak: alpha and gamma wrap around the grid, beta is
not refined at its first and last samples.  It imports nothing of the
system under test.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def real_correlation(f: np.ndarray, g: np.ndarray) -> np.ndarray:
    """Re C on the (2B)^3 Euler grid, in f64."""
    return reference.correlation(np.asarray(f, np.complex128),
                                 np.asarray(g, np.complex128)).real


def neighbours(index, n: int) -> list[tuple[int, int, int]]:
    """The six axis neighbours of a grid point, in the order alpha-,
    alpha+, beta-, beta+, gamma-, gamma+.  Alpha and gamma wrap around; a
    beta neighbour past the grid's edge is the point itself."""
    i, j, k = index
    return [((i - 1) % n, j, k), ((i + 1) % n, j, k),
            (i, max(j - 1, 0), k), (i, min(j + 1, n - 1), k),
            (i, j, (k - 1) % n), (i, j, (k + 1) % n)]


def _offset(ym: float, y0: float, yp: float) -> float:
    """Vertex of the parabola through (-1, ym), (0, y0), (1, yp), clamped
    to half a step; 0 where the three points are on a line."""
    den = ym - 2.0 * y0 + yp
    if den == 0.0:
        return 0.0
    return min(max(0.5 * (ym - yp) / den, -0.5), 0.5)


def refine(re: np.ndarray, index) -> tuple[float, float, float]:
    """ZYZ Euler angles of the grid point ``index`` of Re C, refined."""
    n = re.shape[0]
    B = n // 2
    i, j, k = index
    y0 = re[i, j, k]
    am, ap, bm, bp, gm, gp = (re[p] for p in neighbours(index, n))
    alpha = (i * np.pi / B + np.pi / B * _offset(am, y0, ap)) % (2 * np.pi)
    beta = (2 * j + 1) * np.pi / (4 * B)
    if 0 < j < n - 1:
        beta += np.pi / (2 * B) * _offset(bm, y0, bp)
    gamma = (k * np.pi / B + np.pi / B * _offset(gm, y0, gp)) % (2 * np.pi)
    return alpha, beta, gamma


def match(f: np.ndarray, g: np.ndarray) -> dict:
    """One template: its Re C, peak index and value, and refined angles."""
    re = real_correlation(f, g)
    index = np.unravel_index(int(np.argmax(re)), re.shape)
    index = tuple(int(x) for x in index)
    return {"re": re, "index": index, "peak": float(re[index]),
            "euler": refine(re, index)}
