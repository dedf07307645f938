"""dfs_tpu — TPU-native content-addressed distributed file storage.

A brand-new framework with the capabilities of the reference system
``hiagoluansilva/distributed-file-storage`` (a coordinator-free cluster of
symmetric storage nodes that fragment, SHA-256-verify, cyclically replicate,
list and reconstruct files; see /root/reference/README.md:25-47), re-designed
TPU-first:

- the reference's fixed-N positional fragmenter (StorageNode.java:138-171)
  becomes a pluggable :class:`~dfs_tpu.fragmenter.Fragmenter` interface whose
  TPU backend runs anchored content-defined chunking and strip-scan SHA-256
  as one JAX/Pallas chain (``dfs_tpu.ops``);
- fragments become content-addressed chunks in a dedup-capable store
  (``dfs_tpu.store``), with chunk-granular manifests (``dfs_tpu.meta``) fixing
  the reference defect of digests not being persisted (StorageNode.java:620-626);
- the hand-rolled HTTP/Base64-JSON peer protocol (StorageNode.java:629-642)
  becomes a length-prefixed binary storage plane (``dfs_tpu.comm``) under an
  asyncio node runtime (``dfs_tpu.node``);
- multi-device scaling uses ``jax.sharding.Mesh`` + ``shard_map`` with ICI
  collectives (``dfs_tpu.parallel``), not point-to-point socket calls.
"""

__version__ = "0.1.0"

from dfs_tpu.config import CDCParams, ClusterConfig, NodeConfig  # noqa: F401
