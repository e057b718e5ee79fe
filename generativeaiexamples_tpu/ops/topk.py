"""Maximum-inner-product search on TPU: batched matmul + top-k.

Replaces the reference's Milvus GPU_IVF_FLAT index (knowhere/RAFT,
RetrievalAugmentedGeneration/common/utils.py:198-203,
deploy/compose/docker-compose-vectordb.yaml:57). At RAG corpus sizes
(≤10M chunks) brute-force MIPS is a single MXU-friendly [Q,D]x[D,N]
matmul — exact (recall 1.0, vs IVF's approximate recall) and fast.

Two layouts:
- `mips_topk`: single-device exact search.
- `sharded_mips_topk`: database rows sharded across the mesh "tensor"
  axis; each device computes a local top-k, then the [Q, devices*k]
  candidate set is all-gathered and reduced — the classic distributed
  top-k two-phase reduction, riding ICI.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@functools.partial(jax.jit, static_argnames=("k",))
def mips_topk(queries: jax.Array, database: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k inner products. queries [Q,D], database [N,D] ->
    (scores [Q,k], indices [Q,k])."""
    scores = jnp.einsum(
        "qd,nd->qn", queries, database, preferred_element_type=jnp.float32
    )
    return jax.lax.top_k(scores, k)


class ShardedMIPSIndex:
    """Distributed exact top-k index: DB rows sharded over a mesh axis.

    The database is device_put ONCE at construction (the hot search path
    must not re-transfer gigabytes per query), and the shard_map'd search
    function is jitted once per (k, query-shape) and cached by jax's own
    jit cache (the wrapper function object is stable per index instance).

    Search: local matmul + local top-k per shard, then all_gather of the
    [Q, n_shards*k] candidate set and a final top-k. Index arithmetic
    restores global row ids.
    """

    def __init__(self, database: jax.Array, mesh: Mesh, axis: str = "tensor"):
        self.mesh = mesh
        self.axis = axis
        self.n_shards = mesh.shape[axis]
        N = database.shape[0]
        self.n_rows = N
        self.pad = (-N) % self.n_shards
        if self.pad:  # pad with -inf-scoring rows so any N is accepted
            database = jnp.concatenate(
                [database, jnp.zeros((self.pad, database.shape[1]), database.dtype)]
            )
        self.shard_rows = database.shape[0] // self.n_shards
        self.db = jax.device_put(database, NamedSharding(mesh, P(axis)))
        self._searches: dict = {}

    def _build(self, k: int):
        axis, shard_rows, n_rows = self.axis, self.shard_rows, self.n_rows

        def local(q, db):  # db: [N/n_shards, D]
            s = jnp.einsum("qd,nd->qn", q, db, preferred_element_type=jnp.float32)
            base = jax.lax.axis_index(axis) * shard_rows
            row = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(row < n_rows, s, -jnp.inf)  # mask padding rows
            s, idx = jax.lax.top_k(s, min(k, shard_rows))
            s = jax.lax.all_gather(s, axis, axis=1)  # [Q, n_shards, k]
            idx = jax.lax.all_gather(idx + base, axis, axis=1)
            s = s.reshape(s.shape[0], -1)
            idx = idx.reshape(idx.shape[0], -1)
            best, pos = jax.lax.top_k(s, min(k, n_rows))
            return best, jnp.take_along_axis(idx, pos, axis=1)

        # check_vma off: the all-gather + re-top-k yields replicated
        # outputs the checker cannot prove.
        fn = jax.shard_map(
            local,
            mesh=self.mesh,
            in_specs=(P(), P(axis)),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return jax.jit(fn)

    def search(self, queries: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
        if k not in self._searches:
            self._searches[k] = self._build(k)
        return self._searches[k](queries, self.db)


def sharded_mips_topk(
    queries: jax.Array, database: jax.Array, k: int, mesh: Mesh, axis: str = "tensor"
) -> Tuple[jax.Array, jax.Array]:
    """One-shot convenience wrapper; build a ShardedMIPSIndex for repeated
    searches over the same database."""
    return ShardedMIPSIndex(database, mesh, axis).search(queries, k)
