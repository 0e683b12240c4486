"""Where a kernel wrapper's ``interpret`` argument sends the call.

Every Pallas wrapper in this package takes ``interpret: bool | None``:

* ``None`` (auto): the compiled kernel on a TPU; elsewhere the wrapper's
  exact jnp reference, which is bit-identical and far cheaper than
  interpreting the kernel grid inside a per-timestep scan;
* ``False``: the compiled kernel, whatever the default backend (the
  ahead-of-time compile tests lower this for a described TPU);
* ``True``: the kernel body through the Pallas interpreter.

The choice depends on the backend alone.  Nothing here catches a compile
error: a kernel the TPU compiler refuses fails the launch.
"""
from __future__ import annotations

import jax


def use_reference(interpret: bool | None) -> bool:
    """True iff the wrapper should run its jnp reference, not the kernel."""
    return interpret is None and jax.default_backend() != "tpu"
