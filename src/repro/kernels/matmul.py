"""Tiled MXU matmul (paper pattern: rs_tra — repeated sequential weight
streaming; also the compute-roofline probe).

Classic three-level blocking: grid (M/bm, N/bn, K/bk) with an f32 VMEM
accumulator that persists across the innermost (K) grid dimension.  Block
shapes are the paper's burst knob; MXU wants all of bm/bn/bk to be multiples
of 128 (lane) / 8 (sublane).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], y_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def _matmul_call(x: jax.Array, y: jax.Array, *, bm: int, bn: int,
                 bk: int, interpret: bool) -> jax.Array:
    m, k = x.shape
    k2, n = y.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (x.shape, y.shape, bm, bn, bk)
    n_k = k // bk
    return pl.pallas_call(
        functools.partial(_mm_kernel, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="matmul",
    )(x, y)


def matmul(x: jax.Array, y: jax.Array, *, bm: Optional[int] = None,
           bn: Optional[int] = None, bk: Optional[int] = None,
           interpret: Optional[bool] = None, plan=None) -> jax.Array:
    """Tile sizes left as ``None`` resolve from the cached
    :class:`repro.tune.KernelPlan` for ``(M, N, K, dtype)``;
    ``interpret=None`` ultimately auto-detects the backend."""
    m, k = x.shape
    n = y.shape[1]

    def fit(block, dim):
        """plan tiles must divide the actual dim — halve until they do."""
        block = min(block, dim)
        while dim % block:
            block //= 2
        return max(1, block)

    if (bm is None or bn is None or bk is None
            or (plan is not None and interpret is None)):
        if plan is None:
            from repro.tune import plan_for
            plan = plan_for("matmul", shape_sig=(m, n, k), dtype=str(x.dtype))
        bm = bm if bm is not None else fit(plan.bq, m)
        bn = bn if bn is not None else fit(plan.bq, n)
        bk = bk if bk is not None else fit(plan.bq, k)
        if interpret is None:
            interpret = plan.resolve_interpret()
    if interpret is None:
        from repro.tune import auto_interpret
        interpret = auto_interpret()
    return _matmul_call(x, y, bm=min(bm, m), bn=min(bn, n), bk=min(bk, k),
                        interpret=bool(interpret))
