"""jit'd public wrapper: tiles from the shapes, routes off-TPU to the reference."""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import use_reference
from .kernel import spike_wdm_matmul_pallas, wdm_tiles
from .ref import spike_wdm_matmul_ref


def spike_wdm_matmul(
    wdm: jnp.ndarray,
    stacked: jnp.ndarray,
    *,
    bm: int | None = None,
    bn: int | None = None,
    bk: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """int8 (M, K) @ int8 (K, N) -> int32 (M, N).

    On TPU this runs the Pallas MXU kernel; ``interpret`` routes as in
    :mod:`repro.kernels.dispatch` (auto off-TPU: the jnp reference, the
    same exact int32 accumulation).  The blocks come from the shapes
    (:func:`~.kernel.wdm_tiles`; ``bm``/``bn``/``bk`` override them).  The
    map ``wdm`` is read in place, its edge blocks handled in the kernel;
    only ``stacked`` is padded, to whole column blocks and lane blocks.
    """
    if use_reference(interpret):
        return spike_wdm_matmul_ref(wdm, stacked)
    m, k = wdm.shape
    _, n = stacked.shape
    if k == 0:
        return jnp.zeros((m, n), jnp.int32)
    tm, tk, tn = wdm_tiles(m, k, n)
    bm, bk, bn = bm or tm, bk or tk, bn or tn
    stacked_p = jnp.pad(stacked, ((0, (-k) % bk), (0, (-n) % bn)))
    out = spike_wdm_matmul_pallas(
        wdm, stacked_p, bm=bm, bn=bn, bk=bk, interpret=bool(interpret)
    )
    return out[:, :n]


__all__ = ["spike_wdm_matmul", "spike_wdm_matmul_ref"]
