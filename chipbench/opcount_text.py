"""Bytes the fill of a hashed text column's block needs, from shapes.

A column of free text becomes a dense float32 block of ``num_hash_features
+ 2`` columns a row (token counts, the text length, the null indicator).
Whatever fills it, from whatever encoding of the entries, has to WRITE that
block once: ``n x width x 4`` bytes. The entries' own bytes are left out,
so that no later, smaller encoding takes a share of this roofline over
100%; there is no arithmetic to count (a count is written, not computed).
"""

from __future__ import annotations


def text_fill(shapes: dict) -> tuple[float, float]:
    """``(operations, bytes)`` of one train's text fill at the ``shapes`` a
    run read back: one write of every filled block."""
    blocks, width = shapes["text_blocks"], shapes["text_block_width"]
    return 0.0, 4.0 * shapes["n_rows"] * width * blocks
