"""jit'd public wrapper for the SSD intra-chunk kernel."""
from __future__ import annotations

from ..dispatch import use_reference
from .kernel import ssd_chunk_pallas
from .ref import ssd_chunk_ref


def ssd_chunk(x, b, c, la, *, interpret: bool | None = None):
    """One SSD chunk: (Q,H,P) x (Q,H,N) x (Q,H,N) x (Q,H) ->
    (y (Q,H,P), chunk state (H,N,P)).  f32 operands; ``interpret`` routes
    as in :mod:`repro.kernels.dispatch`."""
    if use_reference(interpret):
        return ssd_chunk_ref(x, b, c, la)
    return ssd_chunk_pallas(x, b, c, la, interpret=bool(interpret))


__all__ = ["ssd_chunk", "ssd_chunk_ref"]
