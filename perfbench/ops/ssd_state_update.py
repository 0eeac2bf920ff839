"""The state-space mixers' state update of one decode step, all layers
(`ops/ssm.py::ssd_state_update`, Mamba-2's recurrence for one position).

Bound by memory: a row's state, `ssm_heads` tiles of `d_state x head_dim`
float32, is read once and written once (4 194 304 B each way a layer at
Falcon-H1-34B's widths); beside it the position's x and y (`ssm_heads x
head_dim` float32 each), its step a head, and B and C a group, which are
a thousandth of that. Operations: about five a state element — the decay's
product, the outer product's product and its sum, C's product and its sum.
"""


def ops_and_bytes(rows: float, layers: int, ssm_heads: int, head_dim: int,
                  d_state: int, groups: int, elem_bytes: int = 4):
    """`rows`: the rows that decode in the step. The kernel passes every
    slot's state, decoding or not; a row that does not decode is no work
    the step needed."""
    elements = ssm_heads * head_dim * d_state
    small = 2 * ssm_heads * head_dim + ssm_heads + 2 * groups * d_state
    moved = rows * layers * (2 * elements + small) * elem_bytes
    return 5.0 * rows * layers * elements, moved
