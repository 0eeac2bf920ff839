"""The delta-rule mixers' state update of one decode step, all layers
(`ops/gated_delta.py::gated_delta_state_update`, the gated delta rule for
one position).

Bound by memory: a row's state, `value_heads` tiles of `key_head_dim x
value_head_dim` float32, is read once and written once (2 097 152 B each
way a layer at Qwen3-Next's widths); beside it the position's q and k
(`key_heads x key_head_dim` float32 each), v and the output (`value_heads
x value_head_dim` each) and g and beta a value head, which are a
hundredth of that. Operations: about eight a state element — the decay's
product, the read-out's product and sum, the write's product and sum, the
output's product and sum.
"""


def ops_and_bytes(rows: float, layers: int, value_heads: int, key_heads: int,
                  key_head_dim: int, value_head_dim: int,
                  elem_bytes: int = 4):
    """`rows`: the rows that decode in the step. The kernel passes every
    slot's state, decoding or not; a row that does not decode is no work
    the step needed. The count is the ALGORITHM's, whatever layout holds
    the state or hands the small operands over."""
    elements = value_heads * key_head_dim * value_head_dim
    small = 2 * key_heads * key_head_dim + 2 * value_heads * value_head_dim \
        + 2 * value_heads
    moved = rows * layers * (2 * elements + small) * elem_bytes
    return 8.0 * rows * layers * elements, moved
