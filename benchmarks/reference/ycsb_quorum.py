"""YCSB workload A on a replica set: the plain reference of the cell
`ycsb_a.wire_rf3`, and the rules that decide whether three replicas hold
what a quorum store's replicas may hold.

Nothing here imports the program, JAX or the sibling references: numpy and
dicts. N replicas, each a dict (key number -> field -> (timestamp,
value)) over the loaded values. A write is stamped by its coordinator
(one clock for the set, strictly rising: the nodes of the cell share a
process) and applied to W replicas at once, the coordinator's own first,
and to the other N - W *later*: after `lag` further operations, or at
`settle()`. A read through a coordinator takes R replicas, the
coordinator's own first, merges them field by field newest timestamp
first, answers the merge and writes it back to those of the replicas it
read that held an older cell (blocking read repair). R + W > N makes
every read see every write
acknowledged before it was sent, whichever coordinators the two went
through; the controls break that, or one replica, or the values.

The replica rule (`replica_checks`). After the window, the drain of what
was in flight and the replay of the hints, every key the window updated
is read from each replica's LOCAL store. A key **diverges** when the
replicas do not hold the same row, or when the row they hold is wrong by
the history's final-row rule (a field's value is no candidate's, or a
candidate's that an acknowledged update follows).
"""
from __future__ import annotations

import numpy as np


class ReplicaSet:
    """`n` replicas read at `r` and written at `w`. The controls:
    `drop_every` and `drop_replica` make one replica acknowledge every
    drop_every-th mutation it is sent without applying it (not at once,
    not later, not by repair); `truncate_to` answers every value cut to
    that many bytes."""

    def __init__(self, loaded: np.ndarray, n: int = 3, w: int = 2,
                 r: int = 2, lag: int = 4, drop_every: int = 0,
                 drop_replica: int = 2, truncate_to: int | None = None):
        self.loaded, self.fields = loaded, loaded.shape[1]
        self.n, self.w, self.r, self.lag = int(n), int(w), int(r), int(lag)
        self.replicas = [dict() for _ in range(self.n)]
        self.clock = 0                  # the coordinators' shared clock
        self.tick = 0                   # operations so far
        self.pending: list = []         # (due tick, replica, key, f, cell)
        self.drop_every, self.drop_replica = int(drop_every), drop_replica
        self.truncate_to = truncate_to
        self.sent_to = [0] * self.n

    # ------------------------------------------------------------ replicas

    def _apply(self, replica: int, keynum: int, field: int,
               cell: tuple) -> None:
        self.sent_to[replica] += 1
        if self.drop_every and replica == self.drop_replica \
                and self.sent_to[replica] % self.drop_every == 0:
            return
        row = self.replicas[replica].setdefault(keynum, {})
        if field not in row or row[field][0] < cell[0]:
            row[field] = cell

    def _deliver(self, upto: int | None) -> None:
        due = [p for p in self.pending if upto is None or p[0] <= upto]
        self.pending = [p for p in self.pending
                        if not (upto is None or p[0] <= upto)]
        for _tick, replica, keynum, field, cell in due:
            self._apply(replica, keynum, field, cell)

    def _order(self, coordinator: int) -> list:
        return [(coordinator + i) % self.n for i in range(self.n)]

    def _cell(self, replica: int, keynum: int, field: int) -> tuple:
        row = self.replicas[replica].get(keynum, {})
        return row.get(field) or (0, self.loaded[keynum, field].tobytes())

    # ---------------------------------------------------------- operations

    def update(self, keynum: int, field: int, value: bytes,
               coordinator: int) -> None:
        self.tick += 1
        self._deliver(self.tick)
        self.clock += 1
        cell = (self.clock, value)
        order = self._order(coordinator)
        for replica in order[:self.w]:
            self._apply(replica, int(keynum), int(field), cell)
        for replica in order[self.w:]:
            self.pending.append((self.tick + self.lag, replica,
                                 int(keynum), int(field), cell))

    def read(self, keynum: int, coordinator: int) -> list:
        self.tick += 1
        self._deliver(self.tick)
        asked = self._order(coordinator)[:self.r]
        row = []
        for f in range(self.fields):
            newest = max((self._cell(rep, int(keynum), f) for rep in asked),
                         key=lambda c: c[0])
            for rep in asked:           # blocking read repair
                if self._cell(rep, int(keynum), f)[0] < newest[0]:
                    self._apply(rep, int(keynum), f, newest)
            row.append(newest[1])
        if self.truncate_to is not None:
            row = [v[:self.truncate_to] for v in row]
        return row

    def settle(self) -> None:
        """The messaging queues drained and the hints replayed."""
        self._deliver(None)

    def local_row(self, replica: int, keynum: int) -> list:
        """What one replica holds, read from it alone."""
        row = [self._cell(replica, int(keynum), f)[1]
               for f in range(self.fields)]
        if self.truncate_to is not None:
            row = [v[:self.truncate_to] for v in row]
        return row


def serial_history(model: ReplicaSet, streams: list, n_ops: int,
                   nodes: int) -> list:
    """The controls' history: the connections' streams interleaved round
    robin and run one after another on the replica set, connection c
    through coordinator c mod `nodes`, operation i taking the instants
    (i, i + 0.5). The records reference/ycsb.py's History takes."""
    ops, i = [], 0
    for index in range(n_ops):
        for conn, s in enumerate(streams):
            keynum, via = int(s["keynum"][index]), conn % nodes
            op = {"conn": conn, "index": index, "keynum": keynum,
                  "sent": float(i), "done": i + 0.5, "ok": True}
            if s["is_read"][index]:
                op.update(kind="read", row=model.read(keynum, via))
            else:
                field = int(s["field"][index])
                value = s["value"][index].tobytes()
                model.update(keynum, field, value, via)
                op.update(kind="update", field=field, value=value)
            ops.append(op)
            i += 1
    return ops


def replicas_diverging(history, local_rows: dict) -> int:
    """`local_rows`: keynum -> [the row each replica holds, read from its
    local store]. `history` is reference/ycsb.py's History of the window
    (its final-row rule judges the row). The keys that diverge."""
    wrong = 0
    for keynum, rows in local_rows.items():
        same = all(r == rows[0] for r in rows[1:])
        if not same or history.final_rows_wrong({keynum: rows[0]}):
            wrong += 1
    return wrong
