"""shard_map data-parallel trainer with int8 + error-feedback gradients.

The pjit path (``dist.steps``) leaves gradient reductions to XLA; this path
makes the reduction explicit with ``shard_map`` so the wire format can be
changed — ``optim.compress`` quantizes each device's local gradient to int8
(with a per-row scale) before the all-reduce, a 4x cut in collective bytes,
and keeps the quantization residual in a per-device error-feedback buffer so
the bias cancels across steps (EF-SGD / 1-bit-Adam lineage).

In the paper's vocabulary this is the unit-size lever applied to the
*collective* stream: the gradient all-reduce is the dominant inter-engine
traffic of a data-parallel step, and shrinking its transaction unit from
fp32 to int8 raises effective inter-chip bandwidth the same way wider HBM
transactions raise DRAM throughput (Fig. 7).

Error-feedback buffers carry a leading per-device axis (``init_error_feedback``
returns ``(n_devices, *param.shape)`` leaves, sharded over "data"): each
device owns its own residual, which is what makes the compression unbiased
per contributor.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.optim import adamw, compress

AXIS = "data"


def init_error_feedback(params, num_devices: Optional[int] = None):
    """Zero residuals, one slice per data-parallel shard (fp32).

    ``num_devices`` must equal the size of the mesh axis the step reduces
    over (``mesh.shape["data"]``); the default of every visible device is
    only right when the whole host is one data-parallel axis."""
    n = num_devices if num_devices is not None else jax.device_count()
    return jax.tree.map(
        lambda p: jnp.zeros((n,) + tuple(p.shape), jnp.float32), params)


def make_dp_train_step(loss_fn: Callable, mesh,
                       opt_cfg: adamw.AdamWConfig,
                       compress_grads: bool = False,
                       axis_name: str = AXIS):
    """step(params, opt_state, err, batch) -> (params, opt_state, err, metrics).

    ``loss_fn(params, batch) -> scalar``; ``batch`` leaves are sharded along
    axis 0 over ``axis_name``; params/opt replicate.  With
    ``compress_grads=True`` each device contributes a dequantized int8 view
    of its (error-corrected) local gradient to the mean; otherwise a plain
    ``pmean``.  Metrics include the modeled wire savings so benchmarks can
    report the collective-bytes column.

    Mesh axes other than ``axis_name`` replicate the batch and therefore
    compute redundantly — this path is data parallelism only; combine it
    with model axes through ``dist.steps`` instead.
    """
    sizes = dict(mesh.shape)
    if axis_name not in sizes:
        raise ValueError(
            f"mesh has axes {sorted(sizes)}, expected data axis "
            f"{axis_name!r}")
    n_shards = sizes[axis_name]

    def local_step(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        loss = jax.lax.pmean(loss, axis_name)
        if compress_grads:
            flat_g, treedef = jax.tree.flatten(grads)
            flat_e = treedef.flatten_up_to(err)
            red, new_e = [], []
            for g, e in zip(flat_g, flat_e):
                r, ne = compress.compressed_psum(
                    g.astype(jnp.float32), e[0], axis_name)
                red.append(r)
                new_e.append(ne[None])
            grads = jax.tree.unflatten(treedef, red)
            new_err = jax.tree.unflatten(treedef, new_e)
        else:
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g.astype(jnp.float32), axis_name),
                grads)
            new_err = err
        new_p, new_opt, om = adamw.update(grads, opt_state, params, opt_cfg)
        metrics = dict(loss=loss, **om)
        return new_p, new_opt, new_err, metrics

    def batch_specs(batch):
        return jax.tree.map(lambda _: P(axis_name), batch)

    def err_specs(err):
        return jax.tree.map(lambda _: P(axis_name), err)

    def rep(tree):
        return jax.tree.map(lambda _: P(), tree)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def step(params, opt_state, err, batch):
        for e in jax.tree.leaves(err):
            if e.shape[0] != n_shards:
                raise ValueError(
                    f"error-feedback leaves carry {e.shape[0]} residual "
                    f"slices but mesh axis {axis_name!r} has {n_shards} "
                    f"shard(s); build them with init_error_feedback(params, "
                    f"num_devices={n_shards})")
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(rep(params), rep(opt_state), err_specs(err),
                      batch_specs(batch)),
            out_specs=(rep(params), rep(opt_state), err_specs(err),
                       P()),
            check_vma=False)
        new_p, new_opt, new_err, metrics = fn(params, opt_state, err, batch)
        if compress_grads:
            metrics = dict(metrics,
                           wire_bytes_saved=jnp.asarray(
                               compress.wire_bytes_saved(params), jnp.float32))
        return new_p, new_opt, new_err, metrics

    return step
