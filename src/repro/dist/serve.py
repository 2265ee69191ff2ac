"""Serve-side distribution policies: one ServeEngine across a device mesh.

The serving twin of ``dist.steps``: where training shards a *step*
function, serving shards the *engine state* — model params by the ``tp``
policy's rules, the KV page pools on their kv-heads dimension, page tables
and sampling state replicated.  In the paper's terms each TP shard is one
more memory channel behind the same request stream: the page pools split
across HBM stacks exactly like a buffer interleaved over DDR banks, so
aggregate KV bandwidth scales with the axis width while the host-side
:class:`~repro.serve.kvcache.PageAllocator` keeps a single global page-id
space (tables stay valid on every shard verbatim).

Determinism contract: the shard_map islands partition only the head
dimension, logits are all-gathered (constrained replicated) before token
selection, and the per-slot PRNG chains never see the mesh — a TP=N drain
is token-identical to the single-device paged engine, greedy and sampled.

DP is deliberately *outside* this class: independent engine replicas
(each optionally TP-sharded) behind one admission queue — see
``launch/serve.py``.  Replicas share no device state, so scaling them is
pure scheduling, not sharding.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.dist.sharding import POLICIES, ShardingPolicy

# pool leaves partition on their kv-heads dim; everything else in the paged
# cache (scale lanes, recurrent state, position rows) replicates
_POOL_LEAVES = ("k_pages", "v_pages")


def _leaf_name(path) -> str:
    names = [str(getattr(p, "key", getattr(p, "name", ""))) for p in path]
    return names[-1] if names else ""


@dataclass(frozen=True)
class ServeMesh:
    """A TP mesh + policy bundle the ServeEngine threads through its state.

    ``mesh`` carries the devices, ``axis`` the mesh axis heads/pools
    partition over, ``policy`` the param-sharding rules (default: the
    train stack's ``tp`` policy, so serve and train agree on layouts).
    """

    mesh: Mesh
    axis: str = "model"
    policy: ShardingPolicy = dataclasses.field(
        default_factory=lambda: POLICIES["tp"])

    # ------------------------------------------------------------------
    @classmethod
    def tp(cls, tp: Optional[int] = None, devices: Optional[Sequence] = None,
           axis: str = "model") -> "ServeMesh":
        """A 1-D TP mesh over ``tp`` devices (default: all of them)."""
        devs: List = list(devices if devices is not None else jax.devices())
        width = int(tp if tp is not None else len(devs))
        if not 1 <= width <= len(devs):
            raise ValueError(
                f"tp={width} needs {width} devices, have {len(devs)}")
        return cls(mesh=Mesh(np.asarray(devs[:width]), (axis,)), axis=axis)

    @property
    def tp_degree(self) -> int:
        return int(self.mesh.shape[self.axis])

    # ------------------------------------------------------------------
    def validate(self, cfg) -> None:
        """The islands need contiguous head blocks per shard: tp must
        divide both head counts (GQA group size stays shard-invariant)."""
        tp = self.tp_degree
        for name, val in (("num_heads", cfg.num_heads),
                          ("num_kv_heads", cfg.num_kv_heads)):
            if val % tp:
                raise ValueError(
                    f"{cfg.name}: {name}={val} not divisible by tp={tp} — "
                    "the paged shard_map islands partition heads in "
                    "contiguous blocks (pad heads or lower tp)")

    def bind(self, bundle):
        """Rebind the bundle's RuntimeFlags for this mesh: the policy's
        activation sharder (GSPMD constraints inside the model) plus the
        mesh/axis the paged dispatches turn into shard_map islands."""
        flags = dataclasses.replace(bundle.flags,
                                    shd=self.policy.sharder(self.mesh),
                                    mesh=self.mesh, tp_axis=self.axis)
        return dataclasses.replace(bundle, flags=flags)

    # ------------------------------------------------------------------
    def param_shardings(self, bundle):
        abs_params, specs = bundle.abstract_params()
        return self.policy.param_shardings(self.mesh, abs_params, specs)

    def shard_params(self, bundle, params):
        """Place existing params (a restored checkpoint, a test's tree) in
        their TP shardings; a no-op for params already placed there (see
        ``launch.serve.init_params``, which draws them in place)."""
        return jax.device_put(params, self.param_shardings(bundle))

    def replicated(self, x):
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def paged_cache_shardings(self, cache):
        """NamedSharding tree for a paged cache (arrays or their
        ShapeDtypeStructs): k/v pools partitioned on their kv-heads dim
        (axis ndim-2: pools are (..., pages, page_size, Hkv, head_dim),
        stacked or not), the rest replicated."""

        def one(path, leaf):
            if _leaf_name(path) in _POOL_LEAVES and leaf.ndim >= 4:
                spec = [None] * leaf.ndim
                spec[leaf.ndim - 2] = self.axis
                return NamedSharding(self.mesh, P(*spec))
            return NamedSharding(self.mesh, P())

        return jax.tree_util.tree_map_with_path(one, cache)

    # ------------------------------------------------------------------
    def page_swap_shardings(self, cache):
        """Shardings governing the host-tier page swap on this mesh.

        Swap-out gathers whole pages along the *page* axis while the pools
        shard on *kv-heads*, so the gather's output keeps the same
        head-stripe layout as the resident pools — each shard moves only
        its own stripe, and the engine's ``device_get`` assembles full
        pages host-side.  Swap-in is the transpose: the scatter's output
        is pinned to these shardings (``jit(..., out_shardings=...)``) so
        streaming host bytes back can never silently replicate a pool
        across the mesh.  This per-shard gather/scatter pair is the page
        transfer primitive disaggregated prefill/decode will reuse to move
        KV between meshes."""
        return self.paged_cache_shardings(cache)
