"""Flash attention, forward and backward, of one training step.

Operations the algorithm needs: the forward has two products (QK^T, PV);
the backward five (QK^T again, since the scores are not kept, then dV,
dP, dQ, dK): seven products of 2·S²·D each per head and row, halved
under a causal mask. The repo's backward is two kernels that each
recompute QK^T and dP; what they recompute beyond the five is not
counted. Bytes: the forward reads q, k, v and writes o; the backward
reads q, k, v, o, do and writes dq, dk, dv (the log-sum-exp rows are
small and left out).
"""


def ops_and_bytes(rows: int, heads: int, seq_len: int, head_dim: int,
                  layers: int, causal: bool = True, elem_bytes: int = 2):
    products = 7.0 * 2.0 * rows * heads * seq_len * seq_len * head_dim
    if causal:
        products /= 2.0
    tensors = 4 + 8
    moved = tensors * rows * heads * seq_len * head_dim * elem_bytes
    return layers * products, layers * float(moved)
