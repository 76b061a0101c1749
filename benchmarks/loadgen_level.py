#!/usr/bin/env python3
"""loadgen.py's sibling for the cells that declare a consistency level:
one connection to ONE coordinator of the cluster under test, every
prepared statement sent at the job's `consistency` (loadgen.py sends the
client's default, ONE). Stays off JAX like its sibling.

    python benchmarks/loadgen_level.py <job.pickle> <result.pickle>

The job: loadgen.py's (host, port, keyspace, statements, ops, seconds,
timeout_s) plus `consistency`, a level's name. The protocol with the
parent ("ready", the common start instant on stdin) and the report (index,
sent, done, ok, rows, err per operation sent) are loadgen.py's.
"""
import pickle
import sys
import time


def main(job_path: str, out_path: str) -> int:
    with open(job_path, "rb") as f:
        job = pickle.load(f)
    from cassandra_tpu.client import Cluster
    session = Cluster(job["host"], job["port"]).connect()
    session._sock.settimeout(job["timeout_s"])
    session.execute(f"USE {job['keyspace']}")
    qid = {n: session.prepare(cql) for n, cql in job["statements"].items()}
    level = job["consistency"]
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    deadline = t0 + job["seconds"]
    out = []
    for index, (stmt, params) in enumerate(job["ops"]):
        if time.monotonic() >= deadline:
            break
        sent = time.monotonic()
        try:
            rows = session.execute_prepared(qid[stmt], params,
                                            consistency=level).rows
            ok, err = True, None
        except Exception as e:      # refused, timed out, connection lost
            rows, ok, err = None, False, f"{type(e).__name__}: {e}"
        done = time.monotonic()
        out.append((index, sent - t0, done - t0, ok, rows, err))
        if not ok and "timed out" in (err or ""):
            break                   # the stream is out of step: stop here
    session.close()
    with open(out_path, "wb") as f:
        pickle.dump(out, f, protocol=pickle.HIGHEST_PROTOCOL)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
