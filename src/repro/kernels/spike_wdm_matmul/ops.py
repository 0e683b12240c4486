"""jit'd public wrapper: pads to MXU tiles, routes off-TPU to the reference."""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import use_reference
from .kernel import spike_wdm_matmul_pallas
from .ref import spike_wdm_matmul_ref


def _pad_to(x: jnp.ndarray, m0: int, m1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def spike_wdm_matmul(
    wdm: jnp.ndarray,
    stacked: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """int8 (M, K) @ int8 (K, N) -> int32 (M, N), auto-padded to tiles.

    On TPU this runs the Pallas MXU kernel; ``interpret`` routes as in
    :mod:`repro.kernels.dispatch` (auto off-TPU: the jnp reference, the
    same exact int32 accumulation).
    """
    if use_reference(interpret):
        return spike_wdm_matmul_ref(wdm, stacked)
    m, k = wdm.shape
    _, n = stacked.shape
    if k == 0:
        return jnp.zeros((m, n), jnp.int32)
    bk_eff = min(bk, max(128, ((k + 127) // 128) * 128))
    wdm_p = _pad_to(wdm, bm, bk_eff)
    stacked_p = _pad_to(stacked, bk_eff, bn)
    out = spike_wdm_matmul_pallas(
        wdm_p, stacked_p, bm=bm, bn=bn, bk=bk_eff, interpret=bool(interpret)
    )
    return out[:m, :n]


__all__ = ["spike_wdm_matmul", "spike_wdm_matmul_ref"]
