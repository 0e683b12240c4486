"""Pallas TPU kernel: the fixed-point ``iaf_psc_exp`` neural update.

The state of a population is laid out as ``(rows, 128)`` int32 maps over
(lane, neuron) pairs, flattened: neurons fill the 128 lanes whatever the
batch, so a batch of 1 wastes none of them.  One VMEM round-trip updates
potential, current and refractory count and emits the spikes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import iaf_psc_exp_ref

LANES = 128


def _iaf_kernel(consts, i_ref, v_ref, c_ref, r_ref, vo, co, ro, zo):
    v, c, r, z = iaf_psc_exp_ref(
        i_ref[...], v_ref[...], c_ref[...], r_ref[...], **consts
    )
    vo[...] = v
    co[...] = c
    ro[...] = r
    zo[...] = z


@functools.partial(jax.jit, static_argnames=("consts", "br", "interpret"))
def iaf_psc_exp_pallas(
    i_in: jnp.ndarray,   # (rows, 128) int32
    v: jnp.ndarray,
    i_syn: jnp.ndarray,
    r: jnp.ndarray,
    *,
    consts: tuple,       # sorted (name, int) pairs of the update's constants
    br: int,
    interpret: bool = False,
):
    rows = i_in.shape[0]
    assert i_in.shape[1] == LANES and rows % br == 0, (i_in.shape, br)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    shape = jax.ShapeDtypeStruct((rows, LANES), jnp.int32)
    return pl.pallas_call(
        functools.partial(_iaf_kernel, dict(consts)),
        grid=(rows // br,),
        in_specs=[spec] * 4,
        out_specs=[spec] * 4,
        out_shape=[shape] * 4,
        interpret=interpret,
    )(i_in, v, i_syn, r)
