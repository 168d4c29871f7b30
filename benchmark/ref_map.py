"""Plain reference of the storm map: a last-writer-wins fold of packed op
words in sequence order. Word = kind | slot << 2 | value << 12; kind 0
sets key ``k<slot>`` to the value, 1 deletes it, 2 clears the map. It
imports nothing of the program."""

from __future__ import annotations


def lww_fold(batches) -> dict[str, int]:
    """The map after applying every batch of words, in order."""
    state: dict[str, int] = {}
    for words in batches:
        for w in words.tolist() if hasattr(words, "tolist") else words:
            kind = w & 3
            if kind == 0:
                state[f"k{(w >> 2) & 0x3FF}"] = (w >> 12) & 0xFFFFF
            elif kind == 1:
                state.pop(f"k{(w >> 2) & 0x3FF}", None)
            elif kind == 2:
                state.clear()
    return state


def swap_last(batches: list) -> list:
    """The control's order: the last two batches sequenced the other way
    round (a break of per-doc total order)."""
    return batches[:-2] + batches[-1:] + batches[-2:-1]
