"""HBM bytes each timed kernel must move per call, from the cell's shapes.

The benchmark's roofline share of a kernel is (calls x these bytes / peak
HBM bandwidth) over the kernel's measured time. The bytes are what the
algorithm needs, not what a layout choice pads them to: a later PR that
drops padding shows as a gain, and a count that is too high would read
above 100% and be refused. All planes are 4-byte words.
"""

from __future__ import annotations

WORD = 4


def map_fold_bytes(docs: int, k: int, slots: int) -> int:
    """The Pallas LWW map fold over ``docs`` rows of ``k`` packed op
    words and ``slots`` key slots: it reads the words and each doc's op
    window (lo, hi, seq base), and reads and writes the present, value and
    value-seq planes and the cleared-seq row."""
    words = docs * k
    windows = 3 * docs
    state = 3 * docs * slots + docs
    return WORD * (words + windows + 2 * state)

