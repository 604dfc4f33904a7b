"""The plain EigenTrust reference, and its lower-precision control.

Both are independent of the code under test: they import nothing of
``protocol_tpu`` and take nothing it made. Semantics (those of the
AttestationStation EigenTrust the program implements):

- duplicate (src, dst) edges are summed; self-loops and non-positive
  values are dropped; every peer is valid;
- each row is normalized to 1; a dangling peer (no out-edge) hands its
  mass uniformly to every *other* peer;
- damping ``alpha`` mixes in uniform pre-trust scaled by the total
  mass, so the mass ``n * initial`` is conserved;
- the iteration starts from ``initial`` at every peer.
"""

from __future__ import annotations

import numpy as np


class ReferenceFailure(Exception):
    pass


def transition(n: int, src, dst, val):
    """(W^T as CSR float64, dangling mask): W[i, j] = val(i->j) / row_i."""
    import scipy.sparse as sp

    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    val = np.asarray(val, dtype=np.float64)
    keep = (src != dst) & (val > 0)
    a = sp.csr_matrix((val[keep], (src[keep], dst[keep])), shape=(n, n))
    a.sum_duplicates()
    row = np.asarray(a.sum(axis=1)).ravel()
    dangling = row == 0
    inv = np.where(dangling, 0.0, 1.0 / np.where(dangling, 1.0, row))
    wt = (sp.diags(inv) @ a).T.tocsr()
    return wt, dangling


def eigentrust_f64(n, src, dst, val, alpha, initial, tol=1e-10,
                   max_iterations=10_000):
    """Float64 power iteration to ``tol`` (relative L1 step). Returns
    ``(scores, iterations)``."""
    wt, dangling = transition(n, src, dst, val)
    s = np.full(n, float(initial))
    for it in range(1, max_iterations + 1):
        corr = (s[dangling].sum() - dangling * s) / max(n - 1, 1)
        new = (1 - alpha) * (wt @ s + corr) + alpha * s.sum() / n
        delta = np.abs(new - s).sum() / np.abs(s).sum()
        s = new
        if delta <= tol:
            return s, it
    raise ReferenceFailure(f"float64 reference did not reach {tol}")


def eigentrust_bf16(n, src, dst, val, alpha, initial, tol,
                    max_iterations):
    """The control: the same iteration with the weights and the score
    vector stored in bfloat16 (products and sums in float32, each new
    score vector rounded to bfloat16) — the mildest step below the
    configuration's float32. Stops like the program: relative L1 step
    <= ``tol`` or ``max_iterations``. Returns ``(scores, iterations)``."""
    import ml_dtypes

    bf16 = ml_dtypes.bfloat16
    wt, dangling = transition(n, src, dst, val)
    wt = wt.astype(np.float32)
    wt.data = wt.data.astype(bf16).astype(np.float32)
    norm = float(n) * float(initial)
    s = np.full(n, float(initial), dtype=np.float32).astype(bf16)
    for it in range(1, max_iterations + 1):
        s32 = s.astype(np.float32)
        corr = (s32[dangling].sum() - dangling * s32) / np.float32(
            max(n - 1, 1))
        new = ((1 - alpha) * (wt @ s32 + corr)
               + alpha * s32.sum() / n).astype(np.float32).astype(bf16)
        delta = np.abs(new.astype(np.float32) - s32).sum() / norm
        s = new
        if delta <= tol:
            break
    return s.astype(np.float64), it


def rel_l1(a, b) -> float:
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return float(np.abs(a - b).sum() / np.abs(b).sum())
