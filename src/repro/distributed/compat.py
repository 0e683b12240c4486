"""The one ``shard_map`` entry point every call site imports.

``sharding`` re-exports it for older call sites.  The installed JAX
(``requirements.txt``) has ``jax.shard_map``; no fallback to
``jax.experimental.shard_map`` is kept.
"""
from __future__ import annotations

import jax


def compat_shard_map(f, *, mesh, in_specs, out_specs, check_vma=True,
                     axis_names=None):
    """``jax.shard_map`` with the keyword set every call site writes."""
    kw = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kw
    )
