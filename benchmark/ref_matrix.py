"""Plain reference of a SharedMatrix: replays one matrix channel's
sequenced op log and returns its grid. It imports nothing of the program.

Semantics of upstream packages/dds/matrix/src/matrix.ts:547
(``processCore``, last-writer-wins cells keyed by (row handle, col
handle)) and permutationvector.ts:38 (rows and cols are each a merge-tree
of handle runs, handles allocated in sequence order, one counter per
axis). Each op resolves its positions in its own (refSeq, client) frame:
a segment is visible when it was inserted at or below the ref or by the
op's own client, and not removed at or below the ref, by the client, or
with the client among the concurrent removers. A remote insert lands
before the first segment at its position unless that segment was removed
at or below the ref (merge-tree breakTie). A cell write whose row or col
is not visible in its frame is dropped.

An op is ``(seq, ref_seq, client, channel_op)``, ``channel_op`` the wire
dict: ``{"target": "rows"|"cols", "type": "insert", "pos", "count"}``,
``{"target": ..., "type": "remove", "start", "end"}``, the
``insertGroup`` / ``removeGroup`` forms with ``ranges``, or
``{"target": "cell", "row", "col", "value"}``.
"""

from __future__ import annotations

import numpy as np


class Vector:
    """One permutation vector: a plain list of handle runs. Columns of
    per-segment fields, mirrored into numpy arrays between vector ops so
    that each cell's lookup is one masked prefix sum."""

    def __init__(self) -> None:
        self.handle: list[int] = []
        self.length: list[int] = []
        self.ins_seq: list[int] = []
        self.ins_client: list[int] = []
        self.rem_seq: list[int] = []      # -1: not removed
        self.rem_client: list[int] = []
        self.overlap: list[set] = []      # further concurrent removers
        self.next_handle = 0
        self._np = None

    def _arrays(self):
        if self._np is None:
            rem = np.asarray(self.rem_seq, np.int64)
            self._np = (np.asarray(self.handle, np.int64),
                        np.asarray(self.length, np.int64),
                        np.asarray(self.ins_seq, np.int64),
                        np.asarray(self.ins_client, np.int64),
                        np.where(rem < 0, np.iinfo(np.int64).max, rem),
                        np.asarray(self.rem_client, np.int64),
                        [i for i, o in enumerate(self.overlap) if o])
        return self._np

    def _vis(self, ref: int, client: int):
        handle, length, ins_seq, ins_client, rem, rem_client, ov = \
            self._arrays()
        ins_vis = (ins_seq <= ref) | (ins_client == client)
        removed = (rem <= ref) | (rem_client == client)
        for i in ov:
            if client in self.overlap[i]:
                removed[i] = True
        return np.where(ins_vis & ~removed, length, 0)

    def handle_at(self, pos: int, ref: int, client: int) -> int | None:
        if not self.handle:
            return None
        vis = self._vis(ref, client)
        cum = np.cumsum(vis)
        i = int(np.searchsorted(cum, pos, side="right"))
        if i >= len(cum):
            return None
        return int(self._np[0][i]) + pos - int(cum[i] - vis[i])

    def _split(self, i: int, off: int) -> None:
        for col in (self.ins_seq, self.ins_client, self.rem_seq,
                    self.rem_client):
            col.insert(i + 1, col[i])
        self.overlap.insert(i + 1, set(self.overlap[i]))
        self.handle.insert(i + 1, self.handle[i] + off)
        self.length.insert(i + 1, self.length[i] - off)
        self.length[i] = off
        self._np = None

    def _boundary(self, pos: int, ref: int, client: int) -> None:
        vis = self._vis(ref, client)
        excl = np.cumsum(vis) - vis
        inside = np.nonzero((excl < pos) & (pos < excl + vis))[0]
        if len(inside):
            i = int(inside[0])
            self._split(i, pos - int(excl[i]))

    def insert(self, pos: int, count: int, seq: int, ref: int,
               client: int) -> None:
        i = len(self.handle)
        if self.handle:
            vis = self._vis(ref, client)
            excl = np.cumsum(vis) - vis
            rem = self._arrays()[4]
            tie = ~(rem <= ref)
            hit = np.nonzero((pos < excl + vis) | ((pos == excl) & tie))[0]
            if len(hit):
                i = int(hit[0])
                if pos > excl[i]:
                    self._split(i, pos - int(excl[i]))
                    i += 1
        self.handle.insert(i, self.next_handle)
        self.length.insert(i, count)
        self.ins_seq.insert(i, seq)
        self.ins_client.insert(i, client)
        self.rem_seq.insert(i, -1)
        self.rem_client.insert(i, -1)
        self.overlap.insert(i, set())
        self.next_handle += count
        self._np = None

    def remove(self, start: int, end: int, seq: int, ref: int,
               client: int) -> None:
        self._boundary(start, ref, client)
        self._boundary(end, ref, client)
        vis = self._vis(ref, client)
        excl = np.cumsum(vis) - vis
        for i in np.nonzero((vis > 0) & (excl >= start) & (excl < end))[0]:
            if self.rem_seq[i] < 0:
                self.rem_seq[i] = seq
                self.rem_client[i] = client
            else:
                self.overlap[i].add(client)
        self._np = None

    def live(self) -> list[int]:
        """Handles of the acked view, in document order."""
        return [h for h, n, r in zip(self.handle, self.length, self.rem_seq)
                if r < 0 for h in range(h, h + n)]


class Matrix:
    """Two vectors and the last-writer-wins cells."""

    def __init__(self) -> None:
        self.rows = Vector()
        self.cols = Vector()
        self.cells: dict[tuple[int, int], object] = {}
        self._clients: dict = {}

    def apply(self, seq: int, ref: int, client, op: dict) -> None:
        c = self._clients.setdefault(client, len(self._clients))
        target = op["target"]
        if target == "cell":
            rh = self.rows.handle_at(op["row"], ref, c)
            ch = self.cols.handle_at(op["col"], ref, c)
            if rh is not None and ch is not None:
                self.cells[(rh, ch)] = op["value"]
            return
        vec = self.rows if target == "rows" else self.cols
        kind = op["type"]
        if kind == "insert":
            vec.insert(op["pos"], op["count"], seq, ref, c)
        elif kind == "insertGroup":
            for pos, count in op["ranges"]:
                vec.insert(pos, count, seq, ref, c)
        elif kind == "removeGroup":
            for start, end in op["ranges"]:
                vec.remove(start, end, seq, ref, c)
        else:
            vec.remove(op["start"], op["end"], seq, ref, c)

    def grid(self) -> list[list]:
        """Dense grid in document order, None for an unset cell."""
        cols = self.cols.live()
        return [[self.cells.get((r, c)) for c in cols]
                for r in self.rows.live()]


def replay(ops) -> list[list]:
    """The grid after applying ``ops`` in the order given."""
    m = Matrix()
    for seq, ref, client, op in ops:
        m.apply(seq, ref, client, op)
    return m.grid()


def swap_last_structural(ops: list) -> list:
    """The control's order: the doc's last pair of structural ops in which
    the later one had seen the earlier (its ref covers the earlier's seq)
    applied the other way round — a break of per-doc total order that the
    grid shows. Falls back to the last two structural ops."""
    vec = [i for i, op in enumerate(ops) if op[3]["target"] != "cell"]
    if len(vec) < 2:
        return list(ops)
    pair = (vec[-2], vec[-1])
    for j in reversed(range(1, len(vec))):
        b = vec[j]
        seen = [a for a in vec[:j] if ops[a][0] <= ops[b][1]]
        if seen:
            pair = (seen[-1], b)
            break
    out = list(ops)
    a, b = pair
    out[a], out[b] = out[b], out[a]
    return out
