"""cassandra_tpu — a TPU-native distributed database framework with the
capabilities of Apache Cassandra (reference: /root/reference, 5.1-dev).

Architecture (not a port):
  - Host runtime (Python + C++) owns files, networking, cluster state.
  - TPU (JAX/XLA/Pallas) is a batch coprocessor for the LSM data plane:
    segmented k-way sort-merge with timestamp reconciliation and tombstone
    purge, chunk codecs and checksums, bloom/hash batches, ANN search.
  - SSTables are *columnar*: fixed-width byte-comparable key lanes +
    metadata lanes + a variable-length payload blob, so device kernels
    operate on sorted fixed-shape arrays instead of row iterators
    (contrast: reference db/rows/* pull-based iterators).

Layer map (mirrors SURVEY.md section 1):
  cql/        CQL language layer         (ref: cql3/)
  cluster/    coordination + placement   (ref: service/, locator/, dht/, gms/)
  storage/    local storage engine       (ref: db/)
  compaction/ compaction + lifecycle     (ref: db/compaction/, db/lifecycle/)
  ops/        device kernels + codecs    (ref: utils/MergeIterator, io/compress/)
  parallel/   mesh lanes + shard planning (ref: db/compaction/ShardManager)
  types/      CQL type system            (ref: db/marshal/)
  utils/      substrate                  (ref: utils/)
"""

__version__ = "0.1.0"
