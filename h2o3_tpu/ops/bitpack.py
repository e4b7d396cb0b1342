"""Small non-negative integers packed into uint32 words: fields laid out in
order at fixed bit widths, no field across two words. The chip prices a
row gather by the row and by each group of 8 int32 columns of it, not by
the byte, so what a gather moves is made narrow this way: the packed row of
the histogram kernels' preparation (``pallas_histogram._pack_row``) and the
slot table of a tree's frontier levels (``booster._frontier_levels``)."""

from __future__ import annotations

import jax.numpy as jnp


def field_bits(n: int) -> int:
    """Bits that hold every value of 0 .. n - 1."""
    return max(1, (n - 1).bit_length())


def word_layout(bits):
    """(word, shift) of each field of ``bits`` laid into 32-bit words in
    order, no field across two words; and the words."""
    at, word, used = [], 0, 0
    for b in bits:
        if used + b > 32:
            word, used = word + 1, 0
        at.append((word, used))
        used += b
    return at, word + 1


def pack_words(fields, bits):
    """uint32 arrays ``fields``, each in 0 .. 2^bits - 1 (an iterable, read
    one field at a time) -> the uint32 words that hold them."""
    at, n = word_layout(bits)
    words = [None] * n
    for x, (w, s) in zip(fields, at):
        v = x.astype(jnp.uint32)
        v = v << s if s else v
        words[w] = v if words[w] is None else words[w] | v
    return words


def unpack_words(word, bits):
    """Inverse of ``pack_words``: ``word(i)``, the i-th uint32 word -> the
    uint32 fields."""
    at, _ = word_layout(bits)
    return [(word(w) >> s) & ((1 << b) - 1) for b, (w, s) in zip(bits, at)]
