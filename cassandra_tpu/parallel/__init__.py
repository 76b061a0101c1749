"""Mesh data plane: `fanout` (the host-thread lane pool and the
`compaction_mesh_devices` demand registry) and `boundaries` (token
boundary planning and the mesh.* shard metrics). Both are jax-free; a
device-engine compaction's lanes dispatch `merge.resident`
(ops/device_write.py), one jax device per lane (compaction/task.py
`_mesh_produce`).
"""
