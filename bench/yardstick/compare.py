"""The numbers that decide ``correct``, each a relative gap."""
from __future__ import annotations

import jax
import numpy as np

#: A leaf whose reference update norm is under this share of the median
#: leaf's is left out of the norm gaps: it moves by round-off alone.
STILL_LEAF = 1e-3


def leaf_norms(tree, base) -> np.ndarray:
    a, b = jax.tree.leaves(tree), jax.tree.leaves(base)
    return np.array([np.linalg.norm(np.asarray(x, np.float64)
                                    - np.asarray(y, np.float64))
                     for x, y in zip(a, b)])


def moving_leaves(ref_norms: np.ndarray) -> np.ndarray:
    return ref_norms >= STILL_LEAF * np.median(ref_norms)


def norm_gap(prog_norms, ref_norms, keep) -> float:
    """Worst leaf of |‖prog‖ - ‖ref‖|, each against the larger of that
    leaf's reference norm and the median leaf's."""
    p, r = np.asarray(prog_norms), np.asarray(ref_norms)
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r)[keep] / scale[keep]))


def rel_gap(prog: float, ref: float) -> float:
    return abs(float(prog) - float(ref)) / abs(float(ref))


def rel_err(prog, ref, base) -> float:
    """‖prog - ref‖ as a share of the reference's move ‖ref - base‖."""
    p, r, b = (np.asarray(x, np.float64).ravel() for x in (prog, ref, base))
    return float(np.linalg.norm(p - r) / np.linalg.norm(r - b))
