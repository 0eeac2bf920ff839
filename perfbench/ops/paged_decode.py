"""Paged decode attention of one decode step, all layers.

Bound by memory: each row reads the K and V pages its context really
fills (whole pages, since a page is the unit the kernel fetches), its
query, and writes its output. Products: 4·H·D per cached token and row.
"""


def ops_and_bytes(tokens_in_pages: float, rows: float, heads: int,
                  kv_heads: int, head_dim: int, layers: int,
                  elem_bytes: int = 2):
    """`tokens_in_pages`: the sum over decoding rows of the context
    length rounded up to whole pages."""
    kv = 2.0 * tokens_in_pages * kv_heads * head_dim * elem_bytes
    qo = 2.0 * rows * heads * head_dim * elem_bytes
    ops = 4.0 * tokens_in_pages * heads * head_dim
    return layers * ops, layers * (kv + qo)
