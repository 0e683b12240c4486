"""Pallas TPU kernel: chunked affine membrane scan.

A state-space-duality move: within a chunk of Q timesteps the reset-free
recurrence ``v[t] = alpha*v[t-1] + c[t]`` equals a lower-triangular matmul

    v = L @ c + alpha^(i+1) * v_carry,    L[i, j] = alpha^(i-j)  (i >= j)

so the MXU evaluates Q steps at once.  The grid is
``(feature_blocks, time_chunks)`` with the time dimension last: TPU
grids iterate sequentially over the trailing axis, so a VMEM scratch row
carries ``v[Q-1]`` from one chunk into the next and is reset whenever a
new feature block starts (``chunk == 0``).

The alpha powers and ``L`` are built on the host, outside the kernel
(Mosaic lowers neither ``cumprod`` nor a vector gather), by a sequential
f32 cumulative product, not ``alpha ** k`` — the chain of f32 multiplies
is exact for alpha in {0, 1} and for dyadic alpha inside the f32 window,
which is what keeps the kernel bit-identical to the sequential scan
under the repo's integer-weight invariant.  They enter the kernel as
inputs with a constant index map, so they stay resident in VMEM across
the grid.  The in-kernel f32 matmul runs at ``HIGHEST`` precision: the
currents are integers that bf16 does not hold exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(l_ref, apow_ref, c_ref, v_ref, carry_ref):
    chunk = pl.program_id(1)

    @pl.when(chunk == 0)
    def _reset():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    v = jax.lax.dot_general(
        l_ref[...], c_ref[...], (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                  # (Q, Fb)
    v = v + apow_ref[...] * carry_ref[...]             # alpha^(i+1) * carry
    v_ref[...] = v
    carry_ref[...] = v[-1:, :]


def scan_operands(alpha: float, q: int):
    """``(L, apow)``: the (Q, Q) lower-triangular ``L[i, j] = alpha^(i-j)``
    and the (Q, 1) carry weights ``alpha^(i+1)``, from one sequential f32
    cumulative product (``pw[0] = 1``)."""
    pw = np.ones(q + 1, np.float32)
    al = np.float32(alpha)
    for k in range(1, q + 1):
        pw[k] = pw[k - 1] * al
    diff = np.arange(q)[:, None] - np.arange(q)[None, :]
    lmat = np.where(diff >= 0, pw[np.maximum(diff, 0)], np.float32(0))
    return lmat.astype(np.float32), pw[1:, None].copy()


@functools.partial(jax.jit, static_argnames=("alpha", "chunk", "bf", "interpret"))
def affine_scan_pallas(
    c: jnp.ndarray,        # (T, F) f32, T % chunk == 0, F % bf == 0
    *,
    alpha: float,
    chunk: int = 128,
    bf: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    steps, feat = c.shape
    grid = (feat // bf, steps // chunk)
    lmat, apow = scan_operands(alpha, chunk)
    return pl.pallas_call(
        _scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((chunk, chunk), lambda ff, tt: (0, 0)),
            pl.BlockSpec((chunk, 1), lambda ff, tt: (0, 0)),
            pl.BlockSpec((chunk, bf), lambda ff, tt: (tt, ff)),
        ],
        out_specs=pl.BlockSpec((chunk, bf), lambda ff, tt: (tt, ff)),
        out_shape=jax.ShapeDtypeStruct((steps, feat), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bf), jnp.float32)],
        interpret=interpret,
    )(jnp.asarray(lmat), jnp.asarray(apow), c)
