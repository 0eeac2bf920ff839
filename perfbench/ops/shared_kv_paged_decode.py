"""Paged decode attention of one decode step over keys and values that
are cached ONCE and read by several layers (Phi-4-mini-flash: layer 17's,
read by that layer and the seven cross layers above it), and the same
count for the window layers' reads of their rings.

A read is ordinary paged attention with `heads` query heads over
`kv_pairs` key/value heads of `pair_dim` (differential attention's pairs:
40 over 10 of 128): bound by memory, each row reads the whole pages its
context fills — or, for a window layer, the whole pages its window
touches — a position `kv_pairs * 2 * pair_dim` values wide (5 120 bytes in
bfloat16), its queries, and writes its outputs. Products: 4 * heads *
pair_dim a cached token and read. The cache is written once a token
whatever the number of reads; the write is not the kernel's.
"""


def ops_and_bytes(tokens_in_pages: float, rows: float, heads: int,
                  kv_pairs: int, pair_dim: int, reads: int,
                  elem_bytes: int = 2):
    """`tokens_in_pages`: the sum over decoding rows of the positions a
    read fetches, in whole pages; `reads`: the layers that read them."""
    kv = tokens_in_pages * kv_pairs * 2 * pair_dim * elem_bytes
    qo = 2.0 * rows * heads * pair_dim * elem_bytes
    ops = 4.0 * tokens_in_pages * heads * pair_dim
    return reads * ops, reads * (kv + qo)
