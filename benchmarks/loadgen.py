#!/usr/bin/env python3
"""One load-generating child: one connection to the node under test,
speaking CQL with the repo's own wire client and nothing else of the
program. Stays off JAX (JAX_PLATFORMS=cpu is set by the parent before the
child starts; the client imports neither JAX nor the store).

    python benchmarks/loadgen.py <job.pickle> <result.pickle>

The job: host, port, keyspace, statements {name: cql}, ops [(statement
name, [param bytes, ...])], seconds, timeout_s. The child connects,
prepares, prints "ready", reads the common start instant
(time.monotonic(), one clock for every process of the machine) from stdin,
and then sends in a closed loop: the next operation when the last
returned. The deadline stops new sends; the operation in flight is
finished. Every operation sent is reported: index, sent, done, ok, rows.
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
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    deadline = t0 + job["seconds"]
    out = []
    for index, (stmt, params) in enumerate(job["ops"]):
        if time.monotonic() >= deadline:
            break
        sent = time.monotonic()
        try:
            rows = session.execute_prepared(qid[stmt], params).rows
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
