"""wire_closedloop: N connections, each a generator child, each sending its
next seeded `ORDER BY v ANN OF ? LIMIT k` when the last returned, against
a node in this process built as tools/noded.py builds one.
"""
from __future__ import annotations

import os
import time

import numpy as np


class State:
    pass


def _vector_bytes(q: np.ndarray) -> bytes:
    return np.ascontiguousarray(q, dtype=">f4").tobytes()


def setup(ctx) -> State:
    import wire
    from cassandra_tpu.storage import cellbatch as cb
    from cassandra_tpu.tools import bulk
    st = State()
    cfg, mix = ctx.config, ctx.traffic
    st.cfg, st.mix, st.scratch = cfg, mix, ctx.scratch
    d, s = cfg["data"], cfg["schema"]
    st.k = int(mix["limit"])
    rng = np.random.default_rng(ctx.seed)
    st.served = wire.ServedNode(os.path.join(ctx.scratch, "node"),
                                s["keyspace"], cfg.get("node_config"))
    for stmt in s["ddl"]:
        st.served.session.execute(stmt)
    table, cfs = st.served.table(s["table"]), st.served.store(s["table"])
    n, dim = int(d["rows"]), int(d["dim"])
    t0 = time.perf_counter()
    st.mat = rng.standard_normal((n, dim), dtype=np.float32)
    vbytes = np.ascontiguousarray(st.mat.astype(">f4")).view(np.uint8) \
        .reshape(n, 4 * dim)
    wire.bulk_load(cfs, cb.merge_sorted([bulk.build_int_batch(
        table, np.arange(n, dtype=np.int64), np.zeros(n, dtype=np.int64),
        vbytes, np.full(n, 1000, dtype=np.int64))]))
    del vbytes
    cfs.reload_sstables()
    ctx.note("load_s", time.perf_counter() - t0)
    st.cql = mix["statement"].format(table=s["table"], limit=st.k)
    conns, per = int(mix["connections"]), int(mix["queries_per_connection"])
    st.queries = rng.standard_normal((conns, per, dim), dtype=np.float32)
    warm = rng.standard_normal((int(mix["warm_queries"]), dim),
                               dtype=np.float32)
    qid = st.served.session.prepare(st.cql)
    walls = []
    for q in warm:                   # the first compiles and builds
        t1 = time.perf_counter()
        st.served.session.execute_prepared(qid, [_vector_bytes(q)])
        walls.append(time.perf_counter() - t1)
    ctx.note("warm_query_s", walls)
    st.children = wire.Children(os.path.join(ctx.scratch, "gen"), ctx.root)
    st.children.start([{
        "host": "127.0.0.1", "port": st.served.port,
        "keyspace": s["keyspace"],
        "statements": {"ann": st.cql}, "seconds": ctx.seconds,
        "timeout_s": float(mix["timeout_s"]),
        "ops": [("ann", [_vector_bytes(q)]) for q in st.queries[c]],
    } for c in range(conns)])
    return st


def window(st: State, ctx) -> dict:
    import wire
    tr = st.mix.get("trace", {})
    t0 = st.children.release()
    th = wire.trace_slice(ctx, t0, tr)
    with ctx.annotate("bench.window.wait_generators"):
        per_child = st.children.collect(
            ctx.seconds + float(st.mix["timeout_s"]) + 60.0)
    if th is not None:
        th.join()
    ops, lost = [], 0
    for c, sent in enumerate(per_child):
        if sent is None:
            lost += 1
            continue
        for index, t_sent, t_done, ok, rows, err in sent:
            ops.append({"conn": c, "index": index, "sent": t_sent,
                        "done": t_done, "ok": ok, "err": err,
                        "ids": [r[0] for r in rows] if ok else None})
    good = [o for o in ops if o["ok"]]
    elapsed = max([o["done"] for o in ops] + [ctx.seconds])
    return {"attempted": len(ops) + lost, "failed": len(ops) - len(good)
            + lost, "ops": ops, "elapsed_s": elapsed,
            "end_to_end": {"ops_s": len(good) / elapsed},
            "detail": {"queries": len(ops), "children_lost": lost,
                       "errors": sorted({o["err"] for o in ops
                                         if o["err"]})[:3]}}


def answer_checks(ref, cfg: dict, mat, queries, served: list, k: int,
                  unanswered: int) -> list:
    """The numbers compared for `served[j]`, the row ids query j was
    answered, against the float64 brute force over the seeded matrix."""
    if served:
        widest, malformed = ref.compare(ref.scores(mat, queries), served, k)
    else:
        widest, malformed = None, 0
    return [
        {"name": "queries_unanswered", "value": unanswered, "limit": 0},
        {"name": "ann_lists_malformed", "value": malformed, "limit": 0,
         "of": len(served)},
        {"name": "ann_widest_score_gap", "value": widest,
         "limit": float(cfg["correct"]["ann_widest_score_gap_limit"]),
         "of": len(served)}]


def check(st: State, ctx, result: dict) -> list:
    """Every answered query of the window (a seeded sample with the
    slowest in it, where there are more than the mix's check_queries)
    against the float64 brute force over the seeded matrix."""
    ref = ctx.load("reference", "ann")
    st.served.close()               # the program's state goes first
    st.served = None
    good = [o for o in result["ops"] if o["ok"]]
    unanswered = result["attempted"] - len(good)
    cap = int(st.mix["check_queries"])
    if len(good) > cap:
        slowest = max(good, key=lambda o: o["done"] - o["sent"])
        pick = np.random.default_rng(ctx.seed + 7).choice(
            len(good), cap - 1, replace=False)
        good = [slowest] + [good[i] for i in pick if good[i] is not slowest]
    qs = np.stack([st.queries[o["conn"], o["index"]] for o in good]) \
        if good else None
    return answer_checks(ref, st.cfg, st.mat, qs, [o["ids"] for o in good],
                         st.k, unanswered)


def control(ctx) -> list:
    """(name, checks) per control, at the cell's own size, no node and no
    chip: the plain reference in the program's place in the stated float32
    (has to read correct), then in the nearest precision below it,
    bfloat16 inputs (has to read not correct), each through the comparison
    `check` makes, over as many queries as a run compares."""
    ref = ctx.load("reference", "ann")
    cfg, mix = ctx.config, ctx.traffic
    d, k = cfg["data"], int(mix["limit"])
    rng = np.random.default_rng(ctx.seed)
    mat = rng.standard_normal((int(d["rows"]), int(d["dim"])),
                              dtype=np.float32)
    qs = rng.standard_normal((int(mix["check_queries"]), int(d["dim"])),
                             dtype=np.float32)
    out = []
    for name in ("float32", "bfloat16"):
        got = ref.scores(mat, qs, precision=name)
        served = [ref.top_k(got[:, j], k) for j in range(len(qs))]
        out.append(("reference_in_place" if name == "float32" else name,
                    answer_checks(ref, cfg, mat, qs, served, k, 0)))
    return out


def close(st: State) -> None:
    st.children.kill()
    if st.served is not None:
        st.served.close()
