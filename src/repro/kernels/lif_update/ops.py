"""jit'd public wrapper for the fused LIF update."""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import use_reference
from .kernel import lif_update_pallas
from .ref import lif_update_ref


def lif_update(
    i_t: jnp.ndarray,
    v: jnp.ndarray,
    z: jnp.ndarray,
    *,
    alpha: float,
    v_th: float,
    bn: int = 256,
    bb: int = 128,
    interpret: bool | None = None,
):
    """Fused V' = I + alpha*V - z*V_th; z' = V' >= V_th.  (N, B) f32 maps.

    On TPU this runs the Pallas VPU kernel; ``interpret`` routes as in
    :mod:`repro.kernels.dispatch` (auto off-TPU: the jnp reference, the
    same elementwise f32 expression, bit-identical).  A dimension smaller
    than its block is one whole block; a larger one is zero-padded up to
    a block multiple, so the VMEM footprint of a grid step never grows
    with the population.
    """
    if use_reference(interpret):
        return lif_update_ref(i_t, v, z, alpha=alpha, v_th=v_th)
    n, b = i_t.shape
    bn_eff, bb_eff = min(bn, n), min(bb, b)
    pn, pb = (-n) % bn_eff, (-b) % bb_eff
    if pn or pb:
        pad = lambda x: jnp.pad(x, ((0, pn), (0, pb)))
        i_t, v, z = pad(i_t), pad(v), pad(z)
    v_new, z_new = lif_update_pallas(
        i_t, v, z, alpha=alpha, v_th=v_th, bn=bn_eff, bb=bb_eff,
        interpret=bool(interpret),
    )
    return v_new[:n, :b], z_new[:n, :b]


__all__ = ["lif_update", "lif_update_ref"]
