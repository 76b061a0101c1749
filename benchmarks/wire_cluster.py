"""wire.py's sibling for the cells that serve from a cluster: N nodes in
this process, each built the way tools/noded.py:main builds one (peers,
seeds, the same DDL with an explicit table id, TcpTransport on loopback)
with a CQL front door of its own, and the generator children that declare
a consistency level (loadgen_level.py).
"""
from __future__ import annotations

import os
import pickle
import socket
import subprocess
import sys
import time
import uuid

import wire

HERE = os.path.dirname(os.path.abspath(__file__))


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


class ServedCluster:
    """`nodes[i]`, `servers[i]`, `ports[i]` (the front doors) and
    `sessions[i]`, one wire session of the parent's own per coordinator.
    The keyspace and the tables exist on every node from its config's
    `ddl`, as independently started noded processes agree on them: by the
    statements' text and the tables' explicit ids."""

    def __init__(self, data_dir: str, keyspace: str, replication: dict,
                 table_ddl: list, cluster: dict, node_config=None):
        from cassandra_tpu.client import Cluster
        from cassandra_tpu.cluster.ring import even_tokens
        from cassandra_tpu.tools.noded import build_node
        from cassandra_tpu.transport.server import CQLServer
        n = int(cluster["nodes"])
        self.keyspace = keyspace
        internode = free_ports(n)
        tokens = even_tokens(n, vnodes=int(cluster["vnodes"]))
        names = [f"node{i + 1}" for i in range(n)]
        options = ", ".join(f"'{k}': {v!r}" if isinstance(v, str)
                            else f"'{k}': {v}"
                            for k, v in replication.items())
        ddl = [f"CREATE KEYSPACE {keyspace} WITH replication = "
               f"{{{options}}}", f"USE {keyspace}"]
        for stmt in table_ddl:
            tid = uuid.uuid5(uuid.NAMESPACE_DNS,
                             f"ctpu.bench.{keyspace}.{len(ddl)}")
            ddl.append(f"{stmt} AND id = {tid}")

        def peer(i: int) -> dict:
            return {"name": names[i], "host": "127.0.0.1",
                    "port": internode[i], "tokens": tokens[i]}
        self.nodes, self.servers, self.sessions = [], [], []
        for i in range(n):
            cfg = dict(peer(i),
                       data_dir=os.path.join(data_dir, names[i]),
                       peers=[peer(j) for j in range(n) if j != i],
                       seeds=[names[0]],
                       gossip_interval=float(cluster["gossip_interval"]),
                       ddl=ddl)
            if node_config:
                cfg["config"] = dict(node_config)
            node, _transport = build_node(cfg)
            self.nodes.append(node)
            self.servers.append(CQLServer(node, "127.0.0.1", 0))
        self.ports = [s.port for s in self.servers]
        self.await_all_alive(float(cluster["liveness_wait_s"]))
        for port in self.ports:
            s = Cluster("127.0.0.1", port).connect()
            s._sock.settimeout(wire.COLD_REQUEST_TIMEOUT_S)
            s.execute(f"USE {keyspace}")
            self.sessions.append(s)

    def await_all_alive(self, wait_s: float) -> None:
        end = time.monotonic() + wait_s
        while time.monotonic() < end:
            if all(a.is_alive(b.endpoint) for a in self.nodes
                   for b in self.nodes if a is not b):
                return
            time.sleep(0.05)
        raise RuntimeError("the nodes did not see one another alive")

    def table(self, name: str):
        return self.nodes[0].schema.get_table(self.keyspace, name)

    def store(self, i: int, name: str):
        """Node i's LOCAL store: no coordinator in the way."""
        return self.nodes[i].engine.store(self.keyspace, name)

    def stop(self, i: int) -> None:
        """Node i goes away: its session, front door, gossip, messaging
        and engine."""
        if self.nodes[i] is None:
            return
        self.sessions[i].close()
        self.servers[i].close()
        self.nodes[i].shutdown()
        self.nodes[i] = None

    def close(self) -> None:
        for i in range(len(self.nodes)):
            self.stop(i)


class LevelChildren(wire.Children):
    """wire.Children with loadgen_level.py as the child: the same
    protocol, a job that carries `consistency`."""

    SCRIPT = os.path.join(HERE, "loadgen_level.py")

    def start(self, jobs: list) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=self.root + os.pathsep
                   + os.environ.get("PYTHONPATH", ""))
        for i, job in enumerate(jobs):
            jp = os.path.join(self.scratch, f"job{i}.pickle")
            rp = os.path.join(self.scratch, f"result{i}.pickle")
            with open(jp, "wb") as f:
                pickle.dump(job, f, protocol=pickle.HIGHEST_PROTOCOL)
            p = subprocess.Popen([sys.executable, self.SCRIPT, jp, rp],
                                 stdin=subprocess.PIPE,
                                 stdout=subprocess.PIPE, env=env, text=True)
            self.procs.append((p, rp))
        for p, _rp in self.procs:
            line = p.stdout.readline()
            if line.strip() != "ready":
                self.kill()
                raise RuntimeError(f"a generator child did not come up: "
                                   f"{line!r}")
