"""Public wrapper for the whole-train affine membrane scan."""
from __future__ import annotations

import jax.numpy as jnp

from ..dispatch import use_reference
from .kernel import affine_scan_pallas
from .ref import affine_scan_ref


def lif_parallel_scan(
    c: jnp.ndarray,
    *,
    alpha: float,
    chunk: int = 128,
    bf: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """All-timesteps v[t] = alpha*v[t-1] + c[t] for c of shape (T, F).

    On TPU this runs the chunked Pallas kernel (MXU lower-triangular
    matmul per chunk, VMEM carry between chunks); ``interpret`` routes as
    in :mod:`repro.kernels.dispatch` (auto off-TPU: the log-depth
    ``associative_scan`` reference, bit-identical under the
    integer-weight invariant).  Time and features shorter than their
    block are one whole block; longer ones are zero-padded up to a block
    multiple (trailing zero currents never reach the kept prefix).
    """
    if use_reference(interpret):
        return affine_scan_ref(c, alpha=alpha)
    steps, feat = c.shape
    ck, bf_eff = min(chunk, steps), min(bf, feat)
    pt, pf = (-steps) % ck, (-feat) % bf_eff
    if pt or pf:
        c = jnp.pad(c, ((0, pt), (0, pf)))
    v = affine_scan_pallas(
        c, alpha=alpha, chunk=ck, bf=bf_eff, interpret=bool(interpret)
    )
    return v[:steps, :feat]


__all__ = ["lif_parallel_scan", "affine_scan_ref"]
