"""Three nodes in this process, each built as tools/noded.py builds one
(peers, seeds, the same DDL with an explicit table id, TcpTransport on
loopback) with a CQL front door of its own: what test_wire_consistency.py
and test_ycsb_rf3_served.py serve their requests from. A helper, not a
test module.

Every node names a small commitlog segment: a node preallocates its
segment, and a session that builds dozens of nodes fills the disk with
32 MiB ones.
"""
import socket
import time
import uuid

from cassandra_tpu.client import Cluster
from cassandra_tpu.cluster.ring import even_tokens
from cassandra_tpu.tools.noded import build_node
from cassandra_tpu.transport.server import CQLServer

KEYSPACE = "ks3"
NODE_CONFIG = {"commitlog_sync": "periodic",
               "commitlog_segment_size": "1MiB"}


def free_ports(n: int) -> list:
    socks = [socket.socket() for _ in range(n)]
    for s in socks:
        s.bind(("127.0.0.1", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def table_ddl(name: str, body: str) -> str:
    """CREATE TABLE with the id every node must agree on."""
    tid = uuid.uuid5(uuid.NAMESPACE_DNS, f"ctpu.test.{KEYSPACE}.{name}")
    return f"CREATE TABLE {KEYSPACE}.{name} ({body}) WITH id = {tid}"


class Ring3:
    """nodes[i], servers[i], and connect(i) for a wire session to node
    i's front door (0-based)."""

    def __init__(self, base_dir, tables: list, rf: int = 3, n: int = 3,
                 gossip_interval: float = 0.1, node_config=None):
        ports, tokens = free_ports(n), even_tokens(n, vnodes=4)
        names = [f"node{i + 1}" for i in range(n)]
        ddl = [f"CREATE KEYSPACE {KEYSPACE} WITH replication = "
               f"{{'class': 'SimpleStrategy', 'replication_factor': {rf}}}"
               ] + list(tables)

        def peer(i):
            return {"name": names[i], "host": "127.0.0.1",
                    "port": ports[i], "tokens": tokens[i]}
        self.nodes, self.servers, self.down = [], [], set()
        for i in range(n):
            cfg = dict(peer(i), data_dir=str(base_dir / names[i]),
                       peers=[peer(j) for j in range(n) if j != i],
                       seeds=[names[0]], gossip_interval=gossip_interval,
                       config=dict(node_config or NODE_CONFIG), ddl=ddl)
            node, _transport = build_node(cfg)
            self.nodes.append(node)
            self.servers.append(CQLServer(node, "127.0.0.1", 0))
        self.await_liveness()

    def await_liveness(self, timeout: float = 20.0) -> None:
        """Every node up sees every other node as it is: up or down."""
        up = [i for i in range(len(self.nodes)) if i not in self.down]
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if all(self.nodes[a].is_alive(self.nodes[b].endpoint)
                   == (b not in self.down)
                   for a in up for b in range(len(self.nodes)) if a != b):
                return
            time.sleep(0.02)
        raise AssertionError("gossip did not converge")

    def connect(self, i: int = 0, timeout: float = 30.0):
        s = Cluster("127.0.0.1", self.servers[i].port).connect()
        s._sock.settimeout(timeout)
        s.execute(f"USE {KEYSPACE}")
        return s

    def stop(self, i: int) -> None:
        """Node i goes away for good (front door, gossip, messaging,
        engine); the others convict it."""
        self.down.add(i)
        self.servers[i].close()
        self.nodes[i].shutdown()

    def close(self) -> None:
        for i in range(len(self.nodes)):
            if i not in self.down:
                self.stop(i)
