"""Plain reference of an exact cosine top-k: numpy in float64 over the
seeded matrix, in blocks of rows; nothing of the program imported.

The number compared is a GAP, not a list: for the i-th row a query was
served, how far its true (float64) cosine score lies below the true i-th
best score. An exact search reads 0 but for float32 rounding between
near-equal neighbours; any approximation reads the size of its score
error. Lists of the wrong length, with a row twice or with an unknown row
are counted apart and never tolerated.
"""
from __future__ import annotations

import numpy as np

BLOCK = 1 << 16


def bf16_round(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest bfloat16 (ties to even), returned as float32."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    bias = ((bits >> np.uint32(16)) & np.uint32(1)) + np.uint32(0x7FFF)
    return ((bits + bias) & np.uint32(0xFFFF0000)).view(np.float32)


def scores(mat: np.ndarray, queries: np.ndarray,
           precision: str = "float64") -> np.ndarray:
    """Cosine scores, (rows, queries). "float64" is the reference;
    "float32" is the reference in the precision the search states (rows
    and queries normalised and multiplied in float32). "bfloat16" is the
    control: rows and queries normalised in float32,
    rounded to bfloat16, products summed in float32 — the TPU's default
    matmul precision, the step below the float32 the search promises."""
    q = np.asarray(queries, dtype=np.float32)
    if precision == "float64":
        q = q.astype(np.float64)
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
        out = np.empty((len(mat), len(q)), dtype=np.float64)
    elif precision in ("float32", "bfloat16"):
        q = q / np.linalg.norm(q, axis=1, keepdims=True)
        if precision == "bfloat16":
            q = bf16_round(q)
        out = np.empty((len(mat), len(q)), dtype=np.float32)
    else:
        raise ValueError(precision)
    for lo in range(0, len(mat), BLOCK):
        m = mat[lo:lo + BLOCK]
        if precision == "float64":
            m = m.astype(np.float64)
            m = m / np.linalg.norm(m, axis=1, keepdims=True)
        else:
            m = m / np.linalg.norm(m, axis=1, keepdims=True)
            if precision == "bfloat16":
                m = bf16_round(m)
        out[lo:lo + BLOCK] = m @ q.T
    return out


def top_k(score_col: np.ndarray, k: int) -> np.ndarray:
    part = np.argpartition(-score_col, k)[:k]
    return part[np.argsort(-score_col[part], kind="stable")]


def compare(ref_scores: np.ndarray, served: list, k: int) -> tuple:
    """(widest gap, malformed lists) over the queries: served[j] is the
    list of row ids query j was answered, ref_scores[:, j] its float64
    scores."""
    widest, malformed = 0.0, 0
    n = ref_scores.shape[0]
    for j, ids in enumerate(served):
        ids = [int(i) for i in ids]
        if len(ids) != k or len(set(ids)) != k \
                or any(i < 0 or i >= n for i in ids):
            malformed += 1
            continue
        col = ref_scores[:, j]
        best = col[top_k(col, k)]
        widest = max(widest, float(np.max(best - col[ids])))
    return widest, malformed
