"""elasticsearch_tpu — a TPU-native distributed search and analytics engine.

A from-scratch re-design of the capabilities of the reference
(Leavesfly/elasticsearch, a fork of elastic/elasticsearch) for JAX/XLA
on TPU. The architecture is documented in ``SURVEY.md`` (layer map §1,
component inventory §2) and the design stance in §7.1: the reference's
*behavior contracts* (REST/JSON API, query-DSL semantics, exact Lucene BM25
scoring incl. the lossy SmallFloat4 norm encoding, durability model, stats
APIs) are preserved, while the implementation uses arrays + collectives
instead of threads + objects.

Layer correspondence (reference → here):
  L1 common libs            → ``elasticsearch_tpu.common``
  L5 index engine           → ``elasticsearch_tpu.index``
  L0 Lucene query kernels   → ``elasticsearch_tpu.ops`` (JAX kernels)
  L7 search execution       → ``elasticsearch_tpu.search``
  P1-P9 parallelism         → ``elasticsearch_tpu.parallel``
  L4 cluster coordination   → ``elasticsearch_tpu.cluster``
  L3 transport RPC          → ``elasticsearch_tpu.transport``
  L8 REST layer             → ``elasticsearch_tpu.rest``
  L2 node runtime           → ``elasticsearch_tpu.node``
"""

from elasticsearch_tpu.version import __version__, Version

__all__ = ["__version__", "Version"]
