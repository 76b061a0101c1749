"""Secondary indexes: equality 2i + TPU vector ANN, storage-attached.

Reference counterpart: index/Index.java SPI + SecondaryIndexManager; the
storage-attached model is SAI's (index/sai/): every sstable carries its
own index component (sstable_index.py), built once from that sstable and
dropped with it — no global rebuild, no unbounded in-memory map, restart
reopens components from disk. The memtable portion is served by scanning
the memtable's sorted cache at query time (small, always fresh; the
reference keeps a trie memtable index for the same role).

The TPU-native twist: the vector index does exact brute-force top-k as a
single batched matmul on the device — for the dimensions and row counts a
single node serves, the MXU makes exhaustive search faster and simpler
than graph ANN, with perfect recall (jvector trades recall for CPU
latency; the MXU removes the tradeoff at this scale).
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np

from ..schema import COL_PARTITION_DEL, COL_ROW_DEL, TableMetadata
from ..utils import pipeline_ledger
from . import sstable_index as ssi

# pipeline `index` (docs/observability.md, span catalogue): `ann` busy =
# the host side of a vector query, stall = blocked on the device's answer
_LED_ANN = pipeline_ledger.ledger("index").stage("ann")
_LED_BUILD = pipeline_ledger.ledger("index").stage("component_build")


class _AttachedIndex:
    """Shared machinery: per-sstable component cache keyed by generation,
    lazily built+loaded; memtable served live."""

    def __init__(self, backend, table: TableMetadata, column: str):
        self.backend = backend
        self.table = table
        self.column = column
        self.col_id = table.columns[column].column_id
        self._cache: dict = {}          # generation -> loaded component
        self._lock = threading.Lock()

    def _cfs(self):
        return self.backend.store(self.table.keyspace, self.table.name)

    def _path(self, desc) -> str:
        return ssi.component_path(desc, self.col_id)

    def _component(self, reader):
        """Load (or build-once, then load) this sstable's component.
        Serialized under the index lock: concurrent first-touch queries
        must not race the build, and a failed load must NEVER cache None
        (that would silently drop the sstable from every future lookup)."""
        gen = reader.desc.generation
        if getattr(reader, "released", False):
            # compaction removed this sstable mid-query (its fd is still
            # open): serve this one query from memory — writing a
            # component for a dead generation would orphan a file
            return self._fresh(reader)
        with self._lock:
            if gen in self._cache:
                return self._cache[gen]
            path = self._path(reader.desc)
            loaded = self._load(path)
            if loaded is None:
                # first-use build: only sstables that predate the index
                # (or lost a component to corruption) land here — new
                # sstables are covered eagerly by ensure_component in
                # the writer tail. The counter pair proves it.
                from ..service.metrics import GLOBAL as _M
                _M.incr("index.lazy_builds")
                with _LED_BUILD.busy("index.component_build",
                                     cells=reader.n_cells):
                    self._build(reader)
                loaded = self._load(path)
            if loaded is None:   # disk refused twice: serve from memory
                loaded = self._fresh(reader)
            self._cache[gen] = loaded
            # drop cache entries for dead sstables
            live = {r.desc.generation for r in self._cfs().live_sstables()}
            for g in [g for g in self._cache if g not in live
                      and g != gen]:
                del self._cache[g]
            return loaded

    def _memtable_entries(self):
        """(value, pk, ck) for live cells of the column in the memtable."""
        mem = self._cfs().memtable.scan()
        if len(mem):
            yield from ssi.iter_column_cells(mem, self.col_id)

    def ensure_component(self, reader) -> bool:
        """Eagerly build+cache this sstable's component (writer tail at
        flush/compaction) so the first query after a restart — or after
        any flush — never pays the build storm. True if a build ran."""
        gen = reader.desc.generation
        if getattr(reader, "released", False):
            return False
        with self._lock:
            if gen in self._cache:
                return False
            path = self._path(reader.desc)
            loaded = self._load(path)
            built = False
            if loaded is None:
                from ..service.metrics import GLOBAL as _M
                _M.incr("index.builds")
                with _LED_BUILD.busy("index.component_build",
                                     cells=reader.n_cells):
                    self._build(reader)
                loaded = self._load(path)
                built = True
            if loaded is None:
                loaded = self._fresh(reader)
            self._cache[gen] = loaded
            return built


class EqualityIndex(_AttachedIndex):
    """Storage-attached 2i: value -> (pk, ck) locators, one component per
    sstable (index/internal hidden-table role, SAI storage model)."""

    def _build(self, reader):
        ssi.build_equality(reader, self.table, self.col_id)

    def _load(self, path):
        return ssi.load_equality(path)

    def _fresh(self, reader):
        out: dict = {}
        for seg in reader.scanner():
            for v, pk, ck, _ts in ssi.iter_column_cells(seg, self.col_id):
                out.setdefault(v, []).append((pk, ck))
        return out

    def lookup(self, value: bytes) -> list:
        out = set()
        for v, pk, ck, _ts in self._memtable_entries():
            if v == value:
                out.add((pk, ck))
        for reader in self._cfs().live_sstables():
            comp = self._component(reader)
            if comp:
                out.update(comp.get(value, ()))
        return sorted(out)


class TextIndex(_AttachedIndex):
    """SASI role: analyzed-term index serving LIKE queries. Candidate
    generation is case-insensitive over ANALYZED terms (CONTAINS mode:
    tokens; PREFIX mode: whole lowercased values); the executor
    re-verifies every candidate against the live row with the
    case-sensitive LIKE predicate, so false positives drop. Token-
    boundary behavior matches SASI: a CONTAINS pattern spanning two
    tokens ('%foo bar%') cannot be served from token terms."""

    def __init__(self, backend, table: TableMetadata, column: str,
                 mode: str = "CONTAINS"):
        super().__init__(backend, table, column)
        self.mode = "PREFIX" if str(mode).upper() == "PREFIX" \
            else "CONTAINS"

    def _path(self, desc):
        return ssi.text_component_path(desc, self.col_id)

    def _build(self, reader):
        ssi.build_text(reader, self.table, self.col_id, self.mode)

    def _load(self, path):
        return ssi.load_text(path)

    def _fresh(self, reader):
        out: dict = {}
        for seg in reader.scanner():
            for v, pk, ck, _ts in ssi.iter_column_cells(seg, self.col_id):
                for term in ssi.analyze(v, self.mode):
                    out.setdefault(term, []).append((pk, ck))
        return out

    def search(self, pattern: str) -> list | None:
        """Locators whose analyzed terms can match the LIKE pattern —
        a SUPERSET; the executor re-verifies with the case-sensitive
        predicate. Returns None when the pattern cannot be served from
        this index (the executor then demands ALLOW FILTERING)."""
        hits = self._term_predicate(pattern)
        if hits is None:
            return None
        out = set()
        for v, pk, ck, _ts in self._memtable_entries():
            if any(hits(t) for t in ssi.analyze(v, self.mode)):
                out.add((pk, ck))
        for reader in self._cfs().live_sstables():
            comp = self._component(reader)
            if comp:
                for term, locs in comp.items():
                    if hits(term):
                        out.update(locs)
        return sorted(out)

    def _term_predicate(self, pattern: str):
        """term -> bool candidate test, or None if unservable. In
        PREFIX mode terms ARE whole lowercased values, so the full
        (lowercased) LIKE pattern applies exactly. In CONTAINS mode a
        value matches only if every token-pure literal piece sits
        inside some token; probing the LONGEST such piece yields a
        correct superset — a pattern with no token-pure piece (e.g.
        '%foo bar%', spanning tokens) cannot be served."""
        low = pattern.lower()
        if self.mode == "PREFIX":
            from ..cql.execution import _like_match
            return lambda term: _like_match(term.decode("utf-8",
                                                        "ignore"), low)
        import re
        pieces = [p for p in low.split("%")
                  if p and re.fullmatch(r"[0-9a-z]+", p)]
        if not pieces:
            return None
        probe = max(pieces, key=len).encode()
        return lambda term: probe in term


SIMILARITIES = ("cosine", "euclidean", "dot_product")
MEM = -1                # owner of a resident row a memtable holds
_PUT, _DEL, _PDEL, _FORGET = range(4)
DELTA_MIN = 4096        # the smallest delta capacity (rows)
PUT_ROWS = 256          # rows per delta put: one program shape
UNSET_SLOTS = 1024      # slots per mask update: one program shape
_NEVER = -(1 << 63)


def similarity_function(options) -> str:
    """The `similarity_function` of an index's OPTIONS (Cassandra 5's
    SAI vector index: COSINE by default, EUCLIDEAN, DOT_PRODUCT) as
    VectorIndex names it; ValueError for any other."""
    opts = {str(k).lower(): v for k, v in (options or {}).items()}
    raw = opts.get("similarity_function", "COSINE")
    name = str(raw).strip().lower()
    if name not in SIMILARITIES:
        raise ValueError(f"unsupported similarity_function {raw!r}: one "
                         f"of COSINE, EUCLIDEAN, DOT_PRODUCT")
    return name


class _Resident:
    """What a VectorIndex keeps on the device, and the host's map of it.

    Slots [0, nb) are the rows the fill found (`base`, exactly that
    shape, uploaded once); [nb, nb + dcap) the rows written since
    (`delta`, a power-of-two capacity allocated at the first change).
    A slot holds one version of one key and is never reused, so a query
    in flight maps its answer through `keys` whatever happens after it;
    a version deleted or overwritten is masked in the validity vectors
    `vb` / `vd` and leaves `slot`. Until the first change there are no
    validity vectors and no delta: the static program scores `base`."""

    def __init__(self, sim, base, keys, ts, owner, dead, pdead,
                 clustered: bool):
        self.sim, self.base, self.keys = sim, base, keys
        self.nb = len(keys)
        self.slot = dict(zip(keys, range(self.nb)))      # live key -> slot
        self.ts, self.owner = ts, owner     # per slot: cell ts, source
        self.alive = np.ones(self.nb, dtype=bool)
        self.dead, self.pdead = dead, pdead  # deletion ts by key / by pk
        self.clustered = clustered
        self.by_pk = None                   # pk -> {ck}, on first need
        self.delta = self.vb = self.vd = None
        self.dcap = self.nd = self.masked = 0

    def snapshot(self) -> tuple:
        return self.base, self.vb, self.delta, self.vd

    def cks(self, pk: bytes) -> list:
        if not self.clustered:
            return [b""]
        if self.by_pk is None:
            self.by_pk = {}
            for p, c in self.slot:
                self.by_pk.setdefault(p, set()).add(c)
        return list(self.by_pk.get(pk, ()))


class VectorIndex(_AttachedIndex):
    """Exact ANN over vector<float, d> columns via device matmul, matrices
    persisted per sstable (index/sai/disk/v1/vector role).

    The rows are kept on the device between queries and follow the
    table's writes (ROADMAP B4): a write is an event the store hands
    over (`on_write`, under its write barrier, before the write is
    acknowledged); the next query applies every event pending, on the
    device, as rows appended to the delta and slots masked, before it
    scores. A flush or a compaction re-points the resident rows to the
    new sstables' components (`refresh`). What still assembles the
    whole table again (a full fill): the first query, and the first
    after a reset (truncate, bulk load or refresh, quarantine, a query
    with another similarity) or after the delta outgrew its bound."""

    def __init__(self, backend, table: TableMetadata, column: str,
                 similarity: str = "cosine"):
        super().__init__(backend, table, column)
        self.dim = table.columns[column].cql_type.dimension
        self.similarity = similarity
        self._entry: _Resident | None = None
        # single-flight fills. Not self._lock: a fill's _gather() ->
        # _component() takes that one, and it is not re-entrant
        self._fill_lock = threading.Lock()
        # the entry's host map and device arrays change under this one
        self._state_lock = threading.Lock()
        # writes since the last drain; kept only while an entry exists
        # or a fill is under way (otherwise the next fill reads them)
        self._pending_lock = threading.Lock()
        self._pending: list = []
        self._recording = False
        self._epoch = 0          # bumped by every reset
        from ..storage.cellbatch import DEATH_FLAGS
        self._death_flags = DEATH_FLAGS
        # the engine's jax device (config `device_ordinal`): the
        # resident rows live there and its programs run there. None =
        # jax's default device
        self.device = getattr(backend, "device", None)

    def _build(self, reader):
        ssi.build_vector(reader, self.table, self.col_id, self.dim)

    def _load(self, path):
        return ssi.load_vector(path)

    def _fresh(self, reader):
        return ssi.scan_vector(reader.scanner(), self.col_id, self.dim)

    # ---------------------------------------------------------- writes --

    def on_write(self, mutation) -> None:
        """A write the store applied to its memtable: kept as an event
        for the next query while there is a resident entry to keep."""
        if not self._recording:
            return
        col, pk, evs = self.col_id, mutation.pk, []
        for ck, column, _path, value, ts, _ldt, _ttl, flags in mutation.ops:
            if column == col:
                if flags & self._death_flags:
                    evs.append((_DEL, pk, ck, ts))
                elif value:
                    evs.append((_PUT, pk, ck, ts, value))
            elif column == COL_ROW_DEL:
                evs.append((_DEL, pk, ck, ts))
            elif column == COL_PARTITION_DEL:
                evs.append((_PDEL, pk, b"", ts))
        if evs:
            with self._pending_lock:
                if self._recording:
                    self._pending.extend(evs)

    def discard(self, locators) -> None:
        """Rows the read-back found dead (a TTL that expired, a range
        tombstone: what no event says): masked by the next query."""
        from ..service.metrics import GLOBAL as _M
        _M.incr("index.ann.readback_dead", len(locators))
        with self._pending_lock:
            if self._recording:
                self._pending.extend((_FORGET, pk, ck, 0)
                                     for pk, ck in locators)

    def reset(self) -> None:
        """The table changed in a way no event describes: drop the
        entry; the next query fills it again."""
        with self._state_lock:
            self._epoch += 1
            self._entry = None
            with self._pending_lock:
                self._recording = False
                self._pending = []

    def _record(self) -> int:
        with self._pending_lock:
            self._pending = []
            self._recording = True
            return self._epoch

    def refresh(self, removed: list, added: list) -> None:
        """A flush or a compaction swapped sstables: the resident rows
        that the new sstables' components carry (same key, same cell
        timestamp) belong to them now; a row they do not hold and no
        known deletion shadows is appended; rows of a removed sstable
        that no new one carries were purged and are masked. No full
        fill: the components are the ones the writer tail just built."""
        e = self._entry
        if e is None or not (removed or added):
            return
        from ..service.metrics import GLOBAL as _M
        with _LED_ANN.busy("index.ann.refresh",
                           items=len(removed) + len(added)) as sp:
            comps = [(r.desc.generation, self._component(r))
                     for r in added]
            gone = {r.desc.generation for r in removed}
            with self._state_lock:
                if self._entry is not e:
                    return
                kept = self._repoint(e, comps, gone, sp)
        if not kept:
            self.reset()        # the delta outgrew its bound: refill
            return
        _M.incr("index.ann.refreshes")

    def _repoint(self, e: _Resident, comps: list, gone: set, sp) -> bool:
        """refresh's work under the state lock; False when the entry had
        to be given up."""
        if not self._drain(e):
            return False
        puts, masks = {}, []
        for gen, comp in comps:
            if comp is None:
                continue
            mat, tss, keys, _dels = comp
            sp.cells += len(keys)
            for i, key in enumerate(keys):
                ts = int(tss[i])
                s = e.slot.get(key)
                if s is not None and e.ts[s] == ts:
                    e.owner[s] = gen
                elif (s is None or e.ts[s] < ts) \
                        and not self._shadowed(e, key, ts) \
                        and not self._put(e, key, ts, mat[i], gen, puts,
                                          masks):
                    return False
        if gone:
            n = e.nb + e.nd
            for s in np.flatnonzero(np.isin(e.owner[:n], list(gone))
                                    & e.alive[:n]):
                self._kill(e, e.keys[int(s)], masks)
        self._push(e, puts, masks)
        return True

    # ------------------------------------------------ the entry's state --

    @staticmethod
    def _shadowed(e: _Resident, key, ts: int) -> bool:
        return ts <= e.dead.get(key, _NEVER) \
            or ts <= e.pdead.get(key[0], _NEVER)

    def _kill(self, e: _Resident, key, masks: list) -> None:
        s = e.slot.pop(key, None)
        if s is not None:
            e.alive[s] = False
            masks.append(s)

    def _put(self, e: _Resident, key, ts: int, row, owner: int,
             puts: dict, masks: list) -> bool:
        """A new version of `key` in the next delta slot (the old one
        masked); False when the delta would outgrow its bound."""
        if e.nd == e.dcap and not self._grow(e):
            return False
        self._kill(e, key, masks)
        s = e.nb + e.nd
        e.nd += 1
        e.keys.append(key)
        e.slot[key] = s
        e.ts[s], e.owner[s], e.alive[s] = ts, owner, True
        if e.by_pk is not None:
            e.by_pk.setdefault(key[0], set()).add(key[1])
        puts[s] = row
        return True

    def _grow(self, e: _Resident) -> bool:
        """Double the delta (the first: a bucket sized from the base);
        a delta past max(base, 2^16) rows is refused: the next query
        fills the whole table again, as a new base."""
        import jax.numpy as jnp
        cap = 2 * e.dcap if e.dcap else \
            max(DELTA_MIN, 1 << max(e.nb // 64 - 1, 0).bit_length())
        if e.dcap and cap > max(e.nb, 1 << 16):
            return False
        grow = cap - e.dcap
        dev = self.device
        if e.delta is None:
            e.delta = jnp.zeros((cap, self.dim), jnp.float32, device=dev)
            e.vd = jnp.zeros(cap, bool, device=dev)
            e.vb = jnp.ones(e.nb, bool, device=dev)
        else:
            e.delta = jnp.concatenate(
                [e.delta, jnp.zeros((grow, self.dim), jnp.float32,
                                    device=dev)])
            e.vd = jnp.concatenate([e.vd, jnp.zeros(grow, bool,
                                                    device=dev)])
        e.ts = np.concatenate([e.ts, np.zeros(grow, np.int64)])
        e.owner = np.concatenate([e.owner, np.full(grow, MEM, np.int64)])
        e.alive = np.concatenate([e.alive, np.zeros(grow, bool)])
        e.dcap = cap
        return True

    def _drain(self, e: _Resident) -> bool:
        """Apply every pending event to `e` (under the state lock), on
        the host map and on the device; False when the entry had to be
        given up (its delta outgrew the bound)."""
        with self._pending_lock:
            events, self._pending = self._pending, []
        if not events:
            return True
        puts: dict = {}
        masks: list = []
        for ev in events:
            kind, pk, ck, ts = ev[:4]
            if kind == _PUT:
                key = (pk, ck)
                s = e.slot.get(key)
                if (s is None or e.ts[s] < ts) \
                        and not self._shadowed(e, key, ts):
                    row = np.frombuffer(ev[4], dtype=">f4")
                    if not self._put(e, key, ts, row, MEM, puts, masks):
                        return False
            elif kind == _PDEL:
                if ts > e.pdead.get(pk, _NEVER):
                    e.pdead[pk] = ts
                for c in e.cks(pk):
                    s = e.slot.get((pk, c))
                    if s is not None and e.ts[s] <= ts:
                        self._kill(e, (pk, c), masks)
            else:
                key = (pk, ck)
                s = e.slot.get(key)
                if kind == _FORGET:
                    if s is None:
                        continue
                    ts = int(e.ts[s])
                if ts > e.dead.get(key, _NEVER):
                    e.dead[key] = ts
                if s is not None and e.ts[s] <= ts:
                    self._kill(e, key, masks)
        self._push(e, puts, masks)
        return True

    def _push(self, e: _Resident, puts: dict, masks: list) -> None:
        """The host map's changes onto the device: rows into the delta,
        then the masks (a row put and masked in one batch lands dead)."""
        from ..service.metrics import GLOBAL as _M
        if not (puts or masks):
            return
        if e.delta is None:
            self._grow(e)           # the first change: the entry goes live
        progs = _live_programs()
        if puts:
            slots = np.fromiter(puts, dtype=np.int64, count=len(puts))
            rows = np.stack([puts[int(s)] for s in slots]).astype(np.float32)
            if e.sim == "cosine":
                rows = rows / np.maximum(
                    np.linalg.norm(rows, axis=1, keepdims=True), 1e-9)
            with _LED_ANN.busy("index.ann.delta", items=len(slots),
                               nbytes=rows.nbytes):
                for lo in range(0, len(slots), PUT_ROWS):
                    at = np.full(PUT_ROWS, e.dcap, np.int32)
                    blk = np.zeros((PUT_ROWS, self.dim), np.float32)
                    n = min(PUT_ROWS, len(slots) - lo)
                    at[:n] = slots[lo:lo + n] - e.nb
                    blk[:n] = rows[lo:lo + n]
                    e.delta, e.vd = progs["delta_put"](e.delta, e.vd,
                                                       at, blk)
            _M.incr("index.ann.delta_rows", len(slots))
        if masks:
            with _LED_ANN.busy("index.ann.mask", items=len(masks)):
                m = np.asarray(masks, dtype=np.int64)
                for part, fill, name in ((m[m < e.nb], e.nb, "vb"),
                                         (m[m >= e.nb] - e.nb, e.dcap, "vd")):
                    for lo in range(0, len(part), UNSET_SLOTS):
                        at = np.full(UNSET_SLOTS, fill, np.int32)
                        n = min(UNSET_SLOTS, len(part) - lo)
                        at[:n] = part[lo:lo + n]
                        setattr(e, name,
                                progs["unset"](getattr(e, name), at))
            e.masked += len(masks)
            _M.incr("index.ann.masked_rows", len(masks))

    # ----------------------------------------------------------- fills --

    def _current(self, similarity: str):
        entry = self._entry
        return entry if entry is not None and entry.sim == similarity \
            else None

    def _gather(self):
        """(matrix, keys, ts, owner, deletions by key, by pk): the newest live version of every key over the memtables
        and every live sstable's persisted component, with the keys a
        newer deletion shadows left out. Assembled by a full fill; the
        stacked matrix is not kept (the device holds it).

        Newest CELL TIMESTAMP wins per (pk, ck): generation order is not
        write order (USING TIMESTAMP), and a stale embedding must not
        rank the row; on equal timestamps the newer source wins like the
        read path's reconcile (memtables outrank sstables), and a
        deletion wins over a cell of its timestamp. The largest source
        is indexed at C speed (one dict from its keys); the others, and
        the deletions, are walked row by row against it."""
        cfs = self._cfs()
        sources = []                 # (mat, ts, keys, owner, rank)
        dead: dict = {}
        pdead: dict = {}
        MEM_RANK = 1 << 62

        def deletions(dels):
            for pk, ck, ts, whole in dels:
                d, k = (pdead, pk) if whole else (dead, (pk, ck))
                if ts > d.get(k, _NEVER):
                    d[k] = ts

        for n, mem in enumerate(cfs.memtables()):
            batch = mem.scan()
            if len(batch):
                m, ts, keys, dels = ssi.scan_vector([batch], self.col_id,
                                                    self.dim)
                sources.append((m, ts, keys, MEM, MEM_RANK - n))
                deletions(dels)
        for reader in cfs.live_sstables():
            gen = reader.desc.generation
            comp = self._component(reader)
            if comp is not None:
                m, ts, keys, dels = comp
                sources.append((m, ts, keys, gen, gen))
                deletions(dels)
        sources = [s for s in sources if s[2]]
        if not sources:
            return (np.zeros((0, self.dim), np.float32), [], None, None,
                    dead, pdead)
        big = max(range(len(sources)), key=lambda b: len(sources[b][2]))
        _bm, bts, bkeys, _bo, _br = sources[big]
        pos = dict(zip(bkeys, range(len(bkeys))))
        if len(pos) != len(bkeys):
            raise RuntimeError("a vector component holds a key twice")
        # per row of the largest source: the source and row that won
        win_src = np.full(len(bkeys), big)
        win_row = np.arange(len(bkeys))
        win_ts = bts.astype(np.int64)
        ranks = [s[4] for s in sources]
        extra: dict = {}             # keys the largest source lacks
        for si, (m, tss, keys, _o, rank) in enumerate(sources):
            if si == big:
                continue
            for i, k in enumerate(keys):
                ts = int(tss[i])
                j = pos.get(k)
                if j is not None:
                    if (ts, rank) > (int(win_ts[j]), ranks[win_src[j]]):
                        win_src[j], win_row[j], win_ts[j] = si, i, ts
                elif k not in extra or (ts, rank) > extra[k][0]:
                    extra[k] = ((ts, rank), si, i)
        # deletions: a key dies when one at least as new shadows it
        killed = np.zeros(len(bkeys), dtype=bool)

        def shadow(key, ts):
            j = pos.get(key)
            if j is not None and win_ts[j] <= ts:
                killed[j] = True
            e = extra.get(key)
            if e is not None and e[0][0] <= ts:
                del extra[key]
        for key, ts in dead.items():
            shadow(key, ts)
        if pdead:
            if self.table.clustering_columns:
                cks: dict = {}
                for pk, ck in list(pos) + list(extra):
                    cks.setdefault(pk, []).append(ck)
                for pk, ts in pdead.items():
                    for ck in cks.get(pk, ()):
                        shadow((pk, ck), ts)
            else:
                for pk, ts in pdead.items():
                    shadow((pk, b""), ts)
        keep = np.flatnonzero(~killed)
        n = len(keep) + len(extra)
        mat = np.empty((n, self.dim), np.float32)
        src = np.concatenate([win_src[keep],
                              np.fromiter((e[1] for e in extra.values()),
                                          np.int64, len(extra))])
        row = np.concatenate([win_row[keep],
                              np.fromiter((e[2] for e in extra.values()),
                                          np.int64, len(extra))])
        for si, (m, _t, _k, _o, _r) in enumerate(sources):
            at = np.flatnonzero(src == si)
            if len(at):
                mat[at] = m[row[at]]
        keys = [bkeys[j] for j in keep] if len(keep) < len(bkeys) \
            else list(bkeys)
        keys.extend(extra)
        ts = np.concatenate([win_ts[keep], np.fromiter(
            (e[0][0] for e in extra.values()), np.int64, len(extra))])
        owners = np.asarray([s[3] for s in sources], dtype=np.int64)
        return mat, keys, ts, owners[src], dead, pdead

    def ann(self, query: np.ndarray, k: int,
            similarity: str | None = None) -> list:
        """Top-k (pk, ck, score), bigger score better. One score + top_k
        on the device — the MXU path (index/sai vector search role) —
        against the rows resident there: filled (assembled, for cosine
        normalised, uploaded) by the first query, while others wait for
        that one fill; then brought up to date with every write
        acknowledged before the query (rows appended, slots masked) and
        scored with the masked rows left out. `similarity` defaults to
        the index's own (its similarity_function option)."""
        from ..service.metrics import GLOBAL as _M
        similarity = similarity or self.similarity
        q = np.asarray(query, dtype=np.float32)
        with _LED_ANN.busy("index.ann.resident") as res, \
                contextlib.ExitStack() as fill:
            with _LED_ANN.busy("index.ann.gather") as sp:
                entry = self._current(similarity)
                if entry is None:
                    # held until the new entry is installed; whoever
                    # waited here finds the entry the holder filled
                    fill.enter_context(self._fill_lock)
                    entry = self._current(similarity)
                filling = entry is None
                if filling:
                    # events from here on are kept: a write this fill
                    # misses is applied after it
                    epoch = self._record()
                    m, keys, ts, owner, dead, pdead = self._gather()
                    sp.cells = len(keys)
                else:
                    sp.cells = entry.nb + entry.nd
            if filling and not keys:
                self.reset()        # an emptied table holds nothing
                return []
            if similarity == "cosine":
                with _LED_ANN.busy("index.ann.normalise") as sp:
                    if filling:
                        # on the host, the expression every query used to
                        # run: the device holds the bytes it scored
                        m = m / np.maximum(
                            np.linalg.norm(m, axis=1, keepdims=True), 1e-9)
                        sp.cells, sp.nbytes = len(m), m.nbytes
                    q = q / max(float(np.linalg.norm(q)), 1e-9)
                    sp.nbytes += q.nbytes
            if filling:
                import jax
                with _LED_ANN.busy("index.ann.upload", cells=len(m),
                                   nbytes=m.nbytes):
                    dev = jax.device_put(m, self.device)
                    dev.block_until_ready()
                entry = _Resident(similarity, dev, keys, ts, owner,
                                  dead, pdead,
                                  bool(self.table.clustering_columns))
                with self._state_lock:
                    if self._epoch == epoch:
                        # the superseded entry's arrays go with it
                        self._entry = entry
                res.nbytes = m.nbytes
                _M.incr("index.ann.resident_fills")
            else:
                res.items = 1
                _M.incr("index.ann.resident_hits")
            overflow = False
            with self._state_lock:
                if self._entry is not entry:
                    # a reset (or a newer fill) replaced it since it was
                    # read, and dropped the events it would need: ask again
                    entry = None
                elif not self._drain(entry):
                    self._entry = entry = None
                    overflow = True
                else:
                    snap = entry.snapshot()
        if entry is None:
            if overflow:
                # the delta outgrew its bound: one more fill, as a new base
                self.reset()
            return self.ann(query, k, similarity)
        base, vb, delta, vd = snap
        n = entry.nb if delta is None else entry.nb + delta.shape[0]
        # the call pushes the query vector and dispatches; the pull
        # blocks on the device's answer
        with _LED_ANN.busy("index.ann.call", cells=n, nbytes=q.nbytes):
            if delta is None:
                vals, idx = ann_program()(base, q, k=min(k, n),
                                          similarity=similarity)
            else:
                vals, idx = _live_programs()["live"](
                    base, vb, delta, vd, q, k=min(k, n),
                    similarity=similarity)
        with _LED_ANN.stall("index.ann.pull"):
            vals, idx = np.asarray(vals), np.asarray(idx)
        keys = entry.keys
        return [(keys[int(i)][0], keys[int(i)][1], float(v))
                for v, i in zip(vals, idx) if v != -np.inf]


def _score(m, q, similarity):
    """One query's scores against a matrix, bigger is better."""
    import jax
    import jax.numpy as jnp
    if similarity == "euclidean":
        # -(|x - q|^2) so bigger is better
        return -jnp.sum((m - q[None, :]) ** 2, axis=1)
    # cosine arrives normalized; dot_product as is
    return jnp.matmul(m, q, precision=jax.lax.Precision.HIGHEST)


_ANN_PROGRAM = None
_LIVE_PROGRAMS: dict = {}


def ann_program():
    """The jitted score + top-k program (defined on first use, like the
    scan kernels), registered as `index.ann` so its compiles per matrix
    shape show in the device program registry. It scores a resident
    entry nothing has changed since its fill (`_live_programs` has the
    masked form).

    The product is taken at HIGHEST precision. Measured on a v5e
    (CHANGES.md PR 21): this matrix x vector form is f32-exact at the
    default too (0 of 40 top-10 lists differ from an f64 brute force),
    but the same product against a MATRIX of queries — the batching
    ROADMAP A9 asks for — rounds its inputs to bf16 at the default and
    reorders 21 of 64 top-10 lists (score error 8e-4 against gaps of
    5e-5); at HIGHEST none, at the same 1.1 ms. An "exact" search must
    not change its answer with the batch width."""
    global _ANN_PROGRAM
    if _ANN_PROGRAM is None:
        from functools import partial

        import jax

        from ..service.profiling import GLOBAL as _kprof

        @partial(jax.jit, static_argnames=("k", "similarity"))
        def program(m, q, k, similarity):
            # named_scope: op names in a profiler trace, nothing else
            with jax.named_scope("score"):
                scores = _score(m, q, similarity)
            with jax.named_scope("top_k"):
                return jax.lax.top_k(scores, k)

        _ANN_PROGRAM = _kprof.wrap("index.ann", program)
    return _ANN_PROGRAM


def _live_programs() -> dict:
    """The programs of an entry that writes have changed, each
    registered in the device program registry: `index.ann.live` (the
    score + top-k of ann_program over the base and the delta, masked
    rows at -inf), `index.ann.delta_put` (PUT_ROWS rows into delta
    slots; padding aims past the end and is dropped) and
    `index.ann.unset` (UNSET_SLOTS slots of a validity vector cleared).
    Their shapes are fixed per base size and delta capacity: no write
    compiles anything until the delta doubles."""
    if not _LIVE_PROGRAMS:
        from functools import partial

        import jax
        import jax.numpy as jnp

        from ..service.profiling import GLOBAL as _kprof

        @partial(jax.jit, static_argnames=("k", "similarity"))
        def live_program(base, valid_b, delta, valid_d, q, k, similarity):
            with jax.named_scope("score"):
                scores = jnp.concatenate([
                    jnp.where(valid_b, _score(base, q, similarity),
                              -jnp.inf),
                    jnp.where(valid_d, _score(delta, q, similarity),
                              -jnp.inf)])
            with jax.named_scope("top_k"):
                return jax.lax.top_k(scores, k)

        @jax.jit
        def delta_put(delta, valid_d, at, rows):
            return (delta.at[at].set(rows, mode="drop"),
                    valid_d.at[at].set(True, mode="drop"))

        @jax.jit
        def unset(valid, at):
            return valid.at[at].set(False, mode="drop")

        _LIVE_PROGRAMS.update(
            live=_kprof.wrap("index.ann.live", live_program),
            delta_put=_kprof.wrap("index.ann.delta_put", delta_put),
            unset=_kprof.wrap("index.ann.unset", unset))
    return _LIVE_PROGRAMS


class IndexManager:
    """Registry (SecondaryIndexManager role). Equality and text indexes
    scan the memtable at query time and attach their components to the
    sstables; a vector index also listens to its store's writes, flushes
    and compactions (`ColumnFamilyStore.index_listeners`) to keep its
    rows on the device current."""

    def __init__(self, backend):
        self.backend = backend
        # (keyspace, table, column) -> index
        self.indexes: dict[tuple, object] = {}
        self.by_name: dict[tuple, tuple] = {}
        self.meta: dict[tuple, dict] = {}   # key -> {custom_class, options}

    def create(self, table: TableMetadata, column: str,
               name: str | None = None, custom_class: str | None = None,
               options: dict | None = None,
               if_not_exists: bool = False):
        from ..types.marshal import VectorType
        key = (table.keyspace, table.name, column)
        if key in self.indexes:
            if if_not_exists:
                return self.indexes[key]
            # silently returning the EXISTING index would hand back the
            # wrong kind (e.g. a 2i where SASI was asked for) and never
            # register the new name — fail like the reference does
            raise ValueError(
                f"an index already exists on "
                f"{table.keyspace}.{table.name}({column})")
        col = table.columns[column]
        options = options or {}
        if custom_class and "sasi" in custom_class.lower():
            # CREATE CUSTOM INDEX ... USING 'SASIIndex'
            # WITH OPTIONS = {'mode': 'CONTAINS'|'PREFIX'}
            idx = TextIndex(self.backend, table, column,
                            mode=options.get("mode", "PREFIX"))
        elif isinstance(col.cql_type, VectorType):
            idx = VectorIndex(self.backend, table, column,
                              similarity=similarity_function(options))
        else:
            idx = EqualityIndex(self.backend, table, column)
        nm = (table.keyspace, name or f"{table.name}_{column}_idx")
        if nm in self.by_name and self.by_name[nm] != key:
            # a silent overwrite would orphan the shadowed index (it
            # stays live but unreachable by name AND vanishes from the
            # persisted schema, which iterates by_name)
            raise ValueError(f"index name {nm[1]!r} already in use")
        self.indexes[key] = idx
        self.by_name[nm] = key
        self.meta[key] = {"custom_class": custom_class,
                          "options": dict(options)}
        if isinstance(idx, VectorIndex):
            cfs = idx._cfs()
            cfs.index_listeners = cfs.index_listeners + (idx,)
        return idx

    def drop(self, keyspace: str, name: str):
        key = self.by_name.pop((keyspace, name), None)
        if key is None:
            raise KeyError(name)
        idx = self.indexes.pop(key, None)
        self.meta.pop(key, None)
        if isinstance(idx, VectorIndex):
            with contextlib.suppress(KeyError):
                cfs = idx._cfs()
                cfs.index_listeners = tuple(
                    x for x in cfs.index_listeners if x is not idx)

    def get(self, keyspace: str, table: str, column: str):
        return self.indexes.get((keyspace, table, column))

    def build_eager(self, table: TableMetadata, reader) -> int:
        """Writer-tail hook: build components for every index on
        `table` against a NEW sstable (flush/compaction/rewrite), so
        the lazy first-use path only ever covers pre-existing sstables.
        Returns how many components were built. Never raises — index
        build failure must not fail the flush that created the data."""
        n = 0
        for (ks, tb, _col), idx in list(self.indexes.items()):
            if ks != table.keyspace or tb != table.name:
                continue
            try:
                if idx.ensure_component(reader):
                    n += 1
            except Exception:
                pass   # first query rebuilds lazily (counted)
        return n
