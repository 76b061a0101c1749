"""What the wire drivers share: a node in this process, started the way
tools/noded.py:main starts one, and the generator children that speak to
it. ServedNode and bulk_load are copies of chip_smoke.py's (PR 21).
"""
from __future__ import annotations

import os
import pickle
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
COLD_REQUEST_TIMEOUT_S = 900.0


class ServedNode:
    """build_node + CQLServer, no "jax_platform" in the config (jax keeps
    the platform it found), RF 1, and one wire session of the parent's
    own for DDL and warm-up."""

    def __init__(self, data_dir: str, keyspace: str, node_config=None):
        from cassandra_tpu.client import Cluster
        from cassandra_tpu.cluster.ring import even_tokens
        from cassandra_tpu.tools.noded import build_node
        from cassandra_tpu.transport.server import CQLServer
        cfg = {"name": "bench", "host": "127.0.0.1", "port": 0,
               "tokens": even_tokens(1, vnodes=4)[0],
               "data_dir": data_dir, "peers": [], "seeds": [],
               "native_port": 0}
        if node_config:
            cfg["config"] = dict(node_config)
        self.keyspace = keyspace
        self.node, self.transport = build_node(cfg)
        self.server = CQLServer(self.node, cfg["host"], cfg["native_port"])
        self.port = self.server.port
        self.session = Cluster("127.0.0.1", self.port).connect()
        # a cold index.ann compile outlasts the client's 10 s (PR 21)
        self.session._sock.settimeout(COLD_REQUEST_TIMEOUT_S)
        self.session.execute(
            f"CREATE KEYSPACE {keyspace} WITH replication = "
            "{'class': 'SimpleStrategy', 'replication_factor': 1}")
        self.session.execute(f"USE {keyspace}")

    def table(self, name: str):
        return self.node.schema.get_table(self.keyspace, name)

    def store(self, name: str):
        return self.node.engine.store(self.keyspace, name)

    def close(self) -> None:
        self.session.close()
        self.server.close()
        self.node.shutdown()


def bulk_load(cfs, batch) -> None:
    from cassandra_tpu.storage.sstable import Descriptor, SSTableWriter
    w = SSTableWriter(Descriptor(cfs.directory, cfs.next_generation()),
                      cfs.table)
    w.append(batch)
    w.finish()


def trace_slice(ctx, t0: float, trace: dict):
    """A thread that traces `trace["seconds"]` of the window from
    `trace["start_s"]` after the children's common start t0
    (time.monotonic()); None on an untraced run. Join it after the
    children are collected."""
    if ctx.tracer is None:
        return None

    def run() -> None:
        time.sleep(max(t0 + float(trace["start_s"]) - time.monotonic(), 0))
        ctx.tracer.start()          # a span that began before is lost
        with ctx.annotate("bench.window.serve"):
            time.sleep(float(trace["seconds"]))
        ctx.tracer.stop()
    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th


class Children:
    """The generator's child processes: one connection each, started and
    ready before the window, released together, waited for after it."""

    def __init__(self, scratch: str, root: str):
        self.scratch, self.root = scratch, root
        self.procs: list = []
        os.makedirs(scratch, exist_ok=True)

    def start(self, jobs: list) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=self.root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        for i, job in enumerate(jobs):
            jp = os.path.join(self.scratch, f"job{i}.pickle")
            rp = os.path.join(self.scratch, f"result{i}.pickle")
            with open(jp, "wb") as f:
                pickle.dump(job, f, protocol=pickle.HIGHEST_PROTOCOL)
            p = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "loadgen.py"), jp, rp],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
                text=True)
            self.procs.append((p, rp))
        for p, _rp in self.procs:
            line = p.stdout.readline()
            if line.strip() != "ready":
                self.kill()
                raise RuntimeError(f"a generator child did not come up: "
                                   f"{line!r}")

    def release(self, lead_s: float = 0.25) -> float:
        """Every child starts its schedule at the returned instant
        (time.monotonic())."""
        t0 = time.monotonic() + lead_s
        for p, _rp in self.procs:
            p.stdin.write(f"{t0!r}\n")
            p.stdin.flush()
        return t0

    def collect(self, timeout_s: float) -> list:
        """Per child, the list of what it sent. Waits for each child; one
        that does not end is killed and its operations are lost (the
        driver counts them as never answered)."""
        out = []
        end = time.monotonic() + timeout_s
        for p, rp in self.procs:
            try:
                p.wait(timeout=max(end - time.monotonic(), 1.0))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            if p.returncode == 0 and os.path.exists(rp):
                with open(rp, "rb") as f:
                    out.append(pickle.load(f))
            else:
                out.append(None)
        self.procs = []
        return out

    def kill(self) -> None:
        for p, _rp in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()
        self.procs = []
