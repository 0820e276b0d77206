"""Plain reference of one skip-gram negative-sampling step, in numpy.

The step ``SkipGram.train_epoch_fused`` compiles, written out: gather the
centre rows of the input table and the context and negative rows of the
output table, the SGNS loss (a batch mean) and its gradients in closed
form, gradients of repeated ids summed, rows written back as
``w -= lr * g`` (plain SGD).  float64 throughout; the tables it is given
hold only the rows a batch touches.

Tolerance (used by ``benchmarks/runners/sgns_train.py``): the program does
this in float32 with the products on the vector unit, not the MXU, so it
agrees to float32 rounding.  ``ROW_RTOL`` is relative to the largest entry
of the touched rows; a hot row sums some hundreds of float32 terms in an
order of the compiler's choosing, which costs a few 1e-6 (measured on the
chip: PERF.md).  A bfloat16 product anywhere would cost 4e-3.
"""

from __future__ import annotations

import numpy as np

__all__ = ["step", "LOSS_RTOL", "ROW_RTOL"]

LOSS_RTOL = 1e-5
ROW_RTOL = 5e-5


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def step(w_in: np.ndarray, w_out: np.ndarray, centers: np.ndarray,
         contexts: np.ndarray, negatives: np.ndarray, lr: float) -> float:
    """Apply one step in place; ids index ``w_in`` / ``w_out`` directly.
    Returns the loss before the update."""
    B, K = negatives.shape
    vc, uo, un = w_in[centers], w_out[contexts], w_out[negatives]
    pos = np.einsum("bd,bd->b", vc, uo)
    neg = np.einsum("bd,bkd->bk", vc, un)
    loss = -(np.sum(np.log(_sigmoid(pos)))
             + np.sum(np.log(_sigmoid(-neg)))) / B
    g_pos = -(1.0 - _sigmoid(pos)) / B
    g_neg = _sigmoid(neg) / B
    d_vc = g_pos[:, None] * uo + np.einsum("bk,bkd->bd", g_neg, un)
    d_uo = g_pos[:, None] * vc
    d_un = g_neg[:, :, None] * vc[:, None, :]
    np.add.at(w_in, centers, -lr * d_vc)
    np.add.at(w_out, contexts, -lr * d_uo)
    np.add.at(w_out, negatives.reshape(-1), -lr * d_un.reshape(B * K, -1))
    return float(loss)
