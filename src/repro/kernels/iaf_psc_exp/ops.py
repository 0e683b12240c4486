"""jit'd public wrapper for the fixed-point ``iaf_psc_exp`` update."""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import use_reference
from .kernel import LANES, iaf_psc_exp_pallas
from .ref import iaf_psc_exp_ref, mul_round

#: Most rows of 128 lanes one grid step of the kernel holds.
BLOCK_ROWS = 512


def iaf_layout(batch: int, n: int) -> int:
    """Rows of the ``(rows, 128)`` state of ``batch`` lanes of ``n``
    neurons: the (lane, neuron) pairs in order, padded to whole blocks of
    8-row tiles (at most :data:`BLOCK_ROWS` rows a block)."""
    rows = -(-batch * n // LANES)
    rows = -(-rows // 8) * 8
    if rows > BLOCK_ROWS:
        rows = -(-rows // BLOCK_ROWS) * BLOCK_ROWS
    return rows


def iaf_psc_exp(
    i_in: jnp.ndarray,
    v: jnp.ndarray,
    i_syn: jnp.ndarray,
    r: jnp.ndarray,
    *,
    consts,
    interpret: bool | None = None,
):
    """One fixed-point ``iaf_psc_exp`` step on ``(rows, 128)`` int32 maps
    (:func:`iaf_layout`); ``consts`` is a
    :class:`repro.core.layer.IafFixed`.  Returns ``(v', i_syn', r', z)``.

    On TPU this runs the Pallas VPU kernel; ``interpret`` routes as in
    :mod:`repro.kernels.dispatch` (auto off-TPU: the jnp reference, the
    same integer expression, bit-identical).
    """
    kw = dict(p22=consts.p22, p21=consts.p21, p11=consts.p11,
              v_drive=consts.v_drive, v_th=consts.v_th,
              v_reset=consts.v_reset, t_ref=consts.t_ref,
              i_bits=consts.i_bits)
    if use_reference(interpret):
        return iaf_psc_exp_ref(i_in, v, i_syn, r, **kw)
    rows = i_in.shape[0]
    return iaf_psc_exp_pallas(
        i_in, v, i_syn, r, consts=tuple(sorted(kw.items())),
        br=min(rows, BLOCK_ROWS), interpret=bool(interpret),
    )


__all__ = ["iaf_layout", "iaf_psc_exp", "iaf_psc_exp_ref", "mul_round"]
