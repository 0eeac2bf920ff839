"""Absorbed latent (MLA) paged decode attention of one decode step, all
sublayers.

Every head of a row reads the same latent rows — `kv_rank + rope` values a
cached position (576 for LongCat-Flash; the pool pads a row to 640 for the
chip's lane tiles, which is the layout's cost and not the algorithm's
need) — in whole pages, since a page is the unit the kernel fetches; the
absorbed query `q~ ‖ q_pe` comes in and `u` goes out a head. Products:
2·H·((kv_rank + rope) + kv_rank) a cached token, scores and p.v. At 121
FLOP a byte against the chip's 240 the bound is memory, narrowly; the
reader takes whichever is larger.
"""


def ops_and_bytes(tokens_in_pages: float, rows: float, heads: int,
                  kv_rank: int, rope: int, sublayers: int,
                  elem_bytes: int = 2):
    """`tokens_in_pages`: the sum over decoding rows of the context
    length rounded up to whole pages."""
    row = kv_rank + rope
    cached = tokens_in_pages * row * elem_bytes
    q_and_u = rows * heads * (row + kv_rank) * elem_bytes
    ops = 2.0 * heads * (row + kv_rank) * tokens_in_pages
    return sublayers * ops, sublayers * (cached + q_and_u)
