"""tests/ycsb_model.py — the tier-1 tests' copy of the YCSB-A plain
reference (benchmarks/reference/ycsb.py is the benchmark's copy, which a
PR that claims a gain may not touch; this one may move with the tests).
Everything below this header is that file's text.
"""
from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
ZIPFIAN_CONSTANT = 0.99
SCRAMBLED_ITEM_COUNT = 10_000_000_000
SCRAMBLED_ZETAN = 26.46902820178302      # zeta(10^10, 0.99), YCSB's constant
LOADED = float("-inf")                   # when the loaded value was "acked"


# ------------------------------------------------------------- the names --

def fnvhash64(vals) -> np.ndarray:
    """Utils.fnvhash64 over an array of non-negative longs: FNV-1 over
    the eight octets, low first, then Math.abs."""
    v = np.asarray(vals).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * np.uint64(FNV_PRIME_64)
            v = v >> np.uint64(8)
    return np.abs(h.astype(np.int64))


def key_names(keynums) -> list:
    """buildKeyName under insertorder=hashed, as bytes."""
    return [b"user%d" % int(h) for h in fnvhash64(keynums)]


# ------------------------------------------------------------ the chooser --

class ScrambledZipfian:
    """ScrambledZipfianGenerator(0, items - 1): draw(rng, n) gives n key
    numbers in [0, items)."""

    def __init__(self, items: int):
        self.items = int(items)
        theta, n = ZIPFIAN_CONSTANT, SCRAMBLED_ITEM_COUNT
        self.theta = theta
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = 1.0 + 0.5 ** theta
        self.eta = (1.0 - (2.0 / n) ** (1.0 - theta)) \
            / (1.0 - zeta2 / SCRAMBLED_ZETAN)

    def ranks(self, rng, n: int) -> np.ndarray:
        """ZipfianGenerator.nextLong over the 10^10 items: rank 0 is the
        most popular."""
        u = rng.random(n)
        uz = u * SCRAMBLED_ZETAN
        out = (SCRAMBLED_ITEM_COUNT
               * (self.eta * u - self.eta + 1.0) ** self.alpha
               ).astype(np.int64)
        out[uz < 1.0 + 0.5 ** self.theta] = 1
        out[uz < 1.0] = 0
        return out

    def draw(self, rng, n: int) -> np.ndarray:
        return fnvhash64(self.ranks(rng, n)) % self.items

    def hottest_share(self) -> float:
        """The share of draws the most popular rank gets: 1 / zeta."""
        return 1.0 / SCRAMBLED_ZETAN


# --------------------------------------------------- seeded data and ops --

def ascii_values(rng, shape) -> np.ndarray:
    """Printable ASCII, as YCSB's RandomByteIterator gives: uint8 in
    [32, 127)."""
    return rng.integers(32, 127, shape, dtype=np.uint8)


def loaded_values(seed: int, records: int, fields: int,
                  length: int) -> np.ndarray:
    """(records, fields, length) uint8: what `ycsb load` wrote, record i
    being key number i."""
    return ascii_values(np.random.default_rng([int(seed), 1]),
                        (records, fields, length))


def op_stream(seed: int, conn: int, n_ops: int, records: int, fields: int,
              length: int, read_share: float) -> dict:
    """One client thread's operations, in order: `is_read` (n,) bool,
    `keynum` (n,), and for the updates `field` (n,) and `value`
    (n, length) uint8 (readallfields=true, writeallfields=false: a read
    asks for every field, an update writes one)."""
    rng = np.random.default_rng([int(seed), 1000 + int(conn)])
    return {"is_read": rng.random(n_ops) < read_share,
            "keynum": ScrambledZipfian(records).draw(rng, n_ops),
            "field": rng.integers(0, fields, n_ops),
            "value": ascii_values(rng, (n_ops, length))}


# --------------------------------------------------------- the dict model --

class Model:
    """usertable as a dict: key number -> field -> last value, over the
    loaded values. `drop_every` and `truncate_to` are the controls: the
    model then acknowledges every drop_every-th update without applying
    it, or answers every value cut to truncate_to bytes."""

    def __init__(self, loaded: np.ndarray, drop_every: int = 0,
                 truncate_to: int | None = None):
        self.loaded = loaded
        self.rows: dict = {}
        self.drop_every, self.truncate_to = int(drop_every), truncate_to
        self.updates = 0

    def update(self, keynum: int, field: int, value: bytes) -> None:
        self.updates += 1
        if self.drop_every and self.updates % self.drop_every == 0:
            return
        self.rows.setdefault(int(keynum), {})[int(field)] = value

    def read(self, keynum: int) -> list:
        over = self.rows.get(int(keynum), {})
        row = [over.get(f, self.loaded[keynum, f].tobytes())
               for f in range(self.loaded.shape[1])]
        if self.truncate_to is not None:
            row = [v[:self.truncate_to] for v in row]
        return row


def serial_history(model: Model, streams: list, n_ops: int) -> list:
    """The controls' history: the connections' streams interleaved round
    robin and run one after another on the model, operation i taking the
    instants (i, i + 0.5). The same records `History` takes from a run."""
    ops, i = [], 0
    for index in range(n_ops):
        for conn, s in enumerate(streams):
            keynum = int(s["keynum"][index])
            op = {"conn": conn, "index": index, "keynum": keynum,
                  "sent": float(i), "done": i + 0.5, "ok": True}
            if s["is_read"][index]:
                op.update(kind="read", row=model.read(keynum))
            else:
                field, value = int(s["field"][index]), \
                    s["value"][index].tobytes()
                model.update(keynum, field, value)
                op.update(kind="update", field=field, value=value)
            ops.append(op)
            i += 1
    return ops


# -------------------------------------------------------- the history rules --

class History:
    """What was sent to each (key, field) and when. An operation is a
    dict: kind ("read" | "update"), keynum, sent, done, ok, and `row` (a
    read's answer: the fields' values in order, or None for no row) or
    `field` and `value` (an update's)."""

    def __init__(self, loaded: np.ndarray, ops: list):
        self.loaded = loaded
        self.fields = loaded.shape[1]
        self.reads = [o for o in ops if o["kind"] == "read"]
        # (keynum, field) -> [(sent, acked or None, value)]
        self.writes: dict = {}
        for o in ops:
            if o["kind"] == "update":
                self.writes.setdefault((o["keynum"], o["field"]), []) \
                    .append((o["sent"], o["done"] if o["ok"] else None,
                             o["value"]))

    def updated_keys(self) -> list:
        return sorted({k for k, _f in self.writes})

    def candidates(self, keynum: int, field: int) -> list:
        """[(sent, acked or None, value)], the loaded value first."""
        return [(LOADED, LOADED, self.loaded[keynum, field].tobytes())] \
            + self.writes.get((keynum, field), [])

    @staticmethod
    def _followed(cand: tuple, others: list, before: float) -> bool:
        """Does an update acknowledged before `before` follow `cand`?"""
        acked = cand[1]
        if acked is None:           # never acknowledged: nothing follows
            return False
        return any(o[1] is not None and o[0] > acked and o[1] < before
                   for o in others)

    def judge_reads(self) -> dict:
        """{"reads_stale", "reads_unknown_value"} over the answered
        reads, each read counted once."""
        stale = unknown = 0
        for r in self.reads:
            if not r["ok"]:
                continue
            row = r["row"]
            if row is None or len(row) != self.fields:
                unknown += 1
                continue
            bad_unknown = bad_stale = False
            for f, got in enumerate(row):
                cands = self.candidates(r["keynum"], f)
                mine = [c for c in cands
                        if c[2] == got and c[0] < r["done"]]
                if not mine:
                    bad_unknown = True
                elif all(self._followed(c, cands, r["sent"])
                         for c in mine):
                    bad_stale = True
            unknown += bad_unknown
            stale += bad_stale and not bad_unknown
        return {"reads_stale": stale, "reads_unknown_value": unknown}

    def final_rows_wrong(self, rows: dict) -> int:
        """`rows`: keynum -> the fields' values read back after the
        window (None for no row). A row is wrong when a field's value is
        no candidate's, or a candidate's that an acknowledged update
        follows."""
        wrong = 0
        for keynum, row in rows.items():
            if row is None or len(row) != self.fields:
                wrong += 1
                continue
            for f, got in enumerate(row):
                cands = self.candidates(keynum, f)
                mine = [c for c in cands if c[2] == got]
                if not mine or all(
                        self._followed(c, cands, float("inf"))
                        for c in mine):
                    wrong += 1
                    break
        return wrong
