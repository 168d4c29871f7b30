"""HBM bytes the serving host's matrix tick (``matrix_tick``) must move
per call, from the cell's shapes.

The tick reads and writes both permutation-vector tables of every grid
(the zamboni rewrites them each call) and reads one tick of ops; of the
cell planes it writes only the cells the tick sets, which a call of the
largest tick width bounds. The lookup of each cell's handles in the
table history is work of the algorithm's choosing and not counted, so
the share this gives over the measured time is a floor of how close the
tick runs to the bandwidth bound. All planes are 4-byte words.
"""

from __future__ import annotations

WORD = 4
#: Words per vector slot: valid, length, ins_seq, ins_client, rem_seq,
#: rem_client, pool_start and one property word.
SLOT_WORDS = 8
#: Words per vector op and per cell write of a tick.
VEC_OP_WORDS = 10
CELL_WORDS = 8


def matrix_tick_bytes(docs: int, vec_slots: int, writers: int,
                      tick_vec: int, tick_cells: int) -> int:
    """One call over ``docs`` grids: two axis tables of ``vec_slots``
    slots (plus the overlap words ``writers`` need) read and written, one
    largest tick of ops read (``tick_vec`` vector ops, ``tick_cells``
    cells), and each cell's value and seq written."""
    overlap_words = -(-writers // 32)
    tables = 2 * docs * vec_slots * (SLOT_WORDS + overlap_words)
    ops = docs * (tick_cells * CELL_WORDS + tick_vec * VEC_OP_WORDS)
    cells = 2 * docs * tick_cells
    return WORD * (2 * tables + ops + cells)
