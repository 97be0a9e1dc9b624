"""What one DAEF fit needs, from its layer sizes, sample count and tenants.

Counted from the algorithm (paper Algorithms 1-2 in the Gram form the
program runs), not from what a compiler emitted.  A multiply-add is two
operations.  Element-wise transforms (activations, their inverses and
derivatives) and the eigendecomposition of the m0 x m0 encoder Gram are left
out of the operations: they are not matrix work and, at these widths, a
rounding error of the total.

For a fit of ``n`` samples through layer sizes m0, m1, ..., m_{L-1}, m0:

* encoder: the Gram X X^T (2 n m0^2) and the projection W1^T X (2 n m0 m1);
* each logistic decoder layer l (m_{l-1} -> m_l, with o = m_{l-1} ROLANN
  outputs and a = m_l + 1 augmented inputs): the stage-1 projection
  (2 n m_{l-1} m_l), the per-output Grams (2 n o a^2, the dominant term),
  the per-output targets M (2 n o a), the o Cholesky solves
  (o (a^3 / 3 + 2 a^2)) and the layer's output (2 n m_{l-1} m_l);
* the linear last layer (a = m_{L-1} + 1 inputs, m0 outputs): its one Gram
  (2 n a^2), M (2 n a m0), one solve (a^3 / 3 + 2 a^2 m0), the
  reconstruction (2 n a m0) and the train errors (3 n m0).
"""
from __future__ import annotations


def fit_terms(layer_sizes, n: int) -> dict[str, float]:
    """Operations of one fit, term by term (see the module docstring)."""
    sizes = tuple(layer_sizes)
    m0, m1 = sizes[0], sizes[1]
    t = {"encoder_gram": 2.0 * n * m0 * m0, "encoder_proj": 2.0 * n * m0 * m1,
         "stage1": 0.0, "hidden_gram": 0.0, "hidden_m": 0.0, "hidden_solve": 0.0,
         "hidden_out": 0.0}
    for li in range(2, len(sizes) - 1):
        o, mi = sizes[li - 1], sizes[li]
        a = mi + 1
        t["stage1"] += 2.0 * n * o * mi
        t["hidden_gram"] += 2.0 * n * o * a * a
        t["hidden_m"] += 2.0 * n * o * a
        t["hidden_solve"] += o * (a ** 3 / 3.0 + 2.0 * a * a)
        t["hidden_out"] += 2.0 * n * o * mi
    a = sizes[-2] + 1
    t["last_gram"] = 2.0 * n * a * a
    t["last_m"] = 2.0 * n * a * m0
    t["last_solve"] = a ** 3 / 3.0 + 2.0 * a * a * m0
    t["recon"] = 2.0 * n * a * m0
    t["errors"] = 3.0 * n * m0
    return t


def fit_flops(layer_sizes, n: int, tenants: int = 1) -> float:
    """Operations of one fit of ``tenants`` models on ``n`` samples each."""
    return tenants * sum(fit_terms(layer_sizes, n).values())

