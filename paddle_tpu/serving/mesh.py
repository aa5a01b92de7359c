"""Tensor-parallel serving mesh: ONE replica spans a multi-chip mesh.

``ServingMesh`` is the serving-side handle for a ``{"mp": N}`` device
mesh (ROADMAP item 1): the decode engine and ``Predictor`` attach one,
and from then on

- **weights** shard by the existing ``distributed.shard`` rule tables
  (``spec_tree`` — the same inference the training path uses), placed
  once with committed ``NamedSharding``s so GSPMD partitions every jit
  entry point from the operand layouts;
- **paged KV pools** shard along the heads: each chip holds
  ``[num_pages, page_size, heads/mp * head_dim]`` of every pool (the
  host-side prefix-cache radix index, refcounts and block tables are
  layout-agnostic and ride unchanged — only device placement changes);
- **activations** are constrained inside the prefill/chunked/verify/
  decode entry points (pool constraints on entry, logits replicated on
  exit) so GSPMD cannot invent a worse layout;
- the mesh axes + spec-tree hash fold into every geometry fingerprint
  and compile-cache key (the PR 10 ``specs_generation`` pattern), so a
  mesh change is a compile-cache MISS while a 1-device mesh degrades to
  today's exact fingerprints byte-for-byte.

The mesh is threaded EXPLICITLY (ctor params, not the thread-local
global mesh): the generation engine dispatches from a worker thread
that never sees the submitting thread's ``set_global_mesh``.

Thread-safety: a ``ServingMesh`` is immutable after construction; the
engine lock (``GenerationServer._lock``) guards all pool mutation as
before — this module never touches engine state.
"""
from __future__ import annotations

from typing import Dict, Optional

__all__ = ["ServingMesh", "serving_mesh_from_flags"]


class ServingMesh:
    """Immutable wrapper over a serving replica's device mesh.

    ``mesh`` may be None (single-shard), a ``jax.sharding.Mesh``, or
    another ``ServingMesh`` (unwrapped). A mesh whose total size is 1
    is INERT: every helper degrades to the identity and ``live`` is
    False, which is what keeps 1-device meshes byte-identical to the
    no-mesh path (fingerprints, cache keys, placement).
    """

    def __init__(self, mesh=None):
        if isinstance(mesh, ServingMesh):
            mesh = mesh.mesh
        self.mesh = mesh

    # ------------------------------------------------------- identity
    @property
    def live(self) -> bool:
        """True when constraints/placement/fingerprint parts apply: a
        real mesh with more than one device."""
        return self.mesh is not None and self.mesh.size > 1

    @property
    def axes(self) -> Dict[str, int]:
        if self.mesh is None:
            return {}
        return {str(k): int(v) for k, v in dict(self.mesh.shape).items()}

    @property
    def mp(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape.get("mp", 1))

    @property
    def devices(self) -> int:
        return int(self.mesh.size) if self.mesh is not None else 1

    def mesh_for_cache_key(self):
        """The mesh folded into ``compile_cache.cache_key``: the real
        mesh when live, None otherwise — so an inert mesh produces the
        exact single-shard key ("none" part)."""
        return self.mesh if self.live else None

    def validate_heads(self, num_heads: int) -> None:
        """Fail fast when the heads axis cannot shard evenly — a
        silently replicated pool under a live mp axis would burn N x
        the KV memory the operator asked to split."""
        if self.live and num_heads % self.mp != 0:
            raise ValueError(
                f"num_heads={num_heads} is not divisible by the "
                f"serving mesh's mp={self.mp}: the paged KV pools "
                f"shard along the heads axis (heads/mp per chip)")

    # ----------------------------------------------------- weight side
    def weight_specs(self, model) -> Dict[str, tuple]:
        """{param-path: spec} through the shard.py rule tables,
        normalized against this mesh (empty when inert)."""
        if not self.live:
            return {}
        from ..distributed.shard import spec_tree
        return spec_tree(model, mesh=self.mesh)

    def place_state(self, params: dict, buffers: dict,
                    specs: Optional[Dict[str, tuple]] = None,
                    model=None):
        """Committed placement of a (params, buffers) snapshot: params
        by their spec tree, buffers replicated. Identity when inert."""
        if not self.live:
            return params, buffers
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed.shard import normalize_spec
        if specs is None:
            specs = self.weight_specs(model)
        placed = {}
        for name, a in params.items():
            spec = normalize_spec(specs.get(name), self.mesh,
                                  tuple(a.shape))
            placed[name] = jax.device_put(
                a, NamedSharding(self.mesh, PartitionSpec(*spec)))
        rep = NamedSharding(self.mesh, PartitionSpec())
        bufs = {name: jax.device_put(a, rep)
                for name, a in buffers.items()}
        return placed, bufs

    # ------------------------------------------------------- pool side
    def pool_specs(self, pools):
        """Matching pytree of specs for a pool pytree: every leaf's
        heads over 'mp' (``ops.paged_attention.kv_pool_heads_spec``
        owns the layout: values and a quantized pool's scale planes,
        stacked or a layer each), normalized, so a heads dim mp doesn't
        divide degrades to replication — but see ``validate_heads``,
        which the engine calls first."""
        import jax
        from ..distributed.shard import normalize_spec
        from ..ops.paged_attention import kv_pool_heads_spec
        return jax.tree_util.tree_map(
            lambda a: normalize_spec(kv_pool_heads_spec(a.ndim),
                                     self.mesh, tuple(a.shape)),
            pools)

    def place_pools(self, k, v):
        """Committed heads-sharded placement of the K/V pool pytrees.
        Identity when inert."""
        if not self.live:
            return k, v
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        specs = self.pool_specs((k, v))
        return jax.tree_util.tree_map(
            lambda a, s: jax.device_put(
                a, NamedSharding(self.mesh, PartitionSpec(*s))),
            (k, v), specs)

    def constrain_pools(self, pools):
        """In-trace activation constraint for pool operands (the jit
        entry points call this on the raw k/v pytrees before wrapping
        them) — pins the heads-axis layout so GSPMD never gathers a
        pool. Identity when inert."""
        if not self.live:
            return pools
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        specs = self.pool_specs(pools)
        return jax.tree_util.tree_map(
            lambda a, s: jax.lax.with_sharding_constraint(
                a, NamedSharding(self.mesh, PartitionSpec(*s))),
            pools, specs)

    def replicate(self, x):
        """In-trace constraint to fully-replicated — the exit pin on
        logits so the (vocab-sharded, under a tied mp-sharded embedding)
        final matmul gathers ONCE inside the executable instead of on
        the host. Identity when inert."""
        if not self.live:
            return x
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        x = getattr(x, "_data", x)   # accept a framework Tensor
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, PartitionSpec()))

    # -------------------------------------------------- fingerprints
    def fingerprint_parts(self, model=None) -> Optional[dict]:
        """Geometry-fingerprint contribution: mesh axes + the weight
        spec-tree hash. None when inert — callers must OMIT the part
        entirely so 1-device meshes reuse today's fingerprints
        byte-for-byte (regression-tested)."""
        if not self.live:
            return None
        from ..distributed.shard import spec_tree_hash
        parts = {"axes": self.axes}
        if model is not None:
            parts["spec_hash"] = spec_tree_hash(self.weight_specs(model))
        return parts

    # ------------------------------------------------- observability
    def per_chip_pool_bytes(self, total_pool_bytes: int,
                            num_heads: int) -> int:
        """Projected per-chip KV-pool residency: the heads axis splits
        evenly (validated), everything else replicates."""
        if not self.live or num_heads % self.mp != 0:
            return int(total_pool_bytes)
        return int(total_pool_bytes) // self.mp

    def statusz(self, kv_pool_bytes: Optional[int] = None,
                num_heads: Optional[int] = None) -> dict:
        out = {"live": self.live, "axes": self.axes,
               "devices": self.devices}
        if kv_pool_bytes is not None and num_heads:
            out["per_chip_kv_pool_bytes"] = self.per_chip_pool_bytes(
                kv_pool_bytes, num_heads)
        return out


def serving_mesh_from_flags(devices=None) -> ServingMesh:
    """Build the replica's serving mesh from ``FLAGS_serving_mesh_mp``:
    an ``{"mp": N}`` mesh over the first N visible devices, or an inert
    ``ServingMesh(None)`` at <=1 (single-shard, today's behavior)."""
    from ..framework.flags import flag_value
    mp = int(flag_value("FLAGS_serving_mesh_mp") or 1)
    if mp <= 1:
        return ServingMesh(None)
    from ..distributed.mesh_utils import build_mesh
    return ServingMesh(build_mesh({"mp": mp}, devices=devices))
