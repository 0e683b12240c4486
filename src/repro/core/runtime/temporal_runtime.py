"""Temporal-parallel LIF runtime: all T timesteps of a layer at once.

Every other launch path pays one ``lax.scan`` iteration per timestep, so
wall-clock is lower-bounded by T sequential LIF steps regardless of how
many cores the placement engine fills.  This module removes that ceiling
for feed-forward segments of the graph plan: a population's whole input
train is projected in one batched contraction and the membrane
trajectory is resolved in log depth via the affine associative scan
(``kernels/lif_parallel_scan``).

The only obstruction is the spike reset ``- z[t-1]*v_th``, which couples
consecutive steps.  Three resolution modes, picked per population by
:func:`choose_temporal_mode`:

``alpha0`` (exact, alpha == 0)
    With no membrane carry-over, ``z[t]`` depends on ``z[t-1]`` only
    through the reset subtraction, so each step is one of two
    precomputable bits: ``A[t] = [i[t] >= v_th]`` (previous step silent)
    or ``B[t] = [i[t] - v_th >= v_th]`` (previous step fired).  The step
    map ``z[t-1] -> z[t]`` is a function {0,1}->{0,1}; encoding it as
    the pair ``(f(0), f(1))`` makes composition associative and exact in
    f32 0/1 arithmetic, so one associative scan resolves the whole spike
    train.  Bit-identical to the sequential kernel: the single f32
    subtraction ``i[t] - v_th`` is exactly what ``lif_update`` computes
    when ``alpha*v`` vanishes.

``count`` (exact, alpha == 1, non-negative weights, integer v_th >= 1)
    Perfect integration with subtractive reset is a counting process:
    with ``U[t] = cumsum(i)`` (nondecreasing when all currents are
    >= 0), the cumulative spike count obeys ``N[t] = max(N[t-1],
    min(N[t-1] + 1, U[t] // v_th))``, whose closed form is ``N[t] = t +
    min(1, cummin(U[s]//v_th - s))``.  Pure int32 arithmetic — cumsum,
    cummin, one subtraction — hence bit-identical to the sequential f32
    kernel while magnitudes stay inside the 2^24 integer window (the
    repo's standing invariant).

``iterative`` (bounded fixed point, everything else)
    Pass k feeds the spikes of pass k-1 into the reset currents
    ``c[t] = i[t] - z[t-1]*v_th`` and re-runs the reset-free affine
    scan.  After pass k the first k timesteps are final (induction: step
    t's inputs are final once steps < t are), so the iteration converges
    in at most T+1 passes regardless of float rounding; in practice
    spike trains settle in a handful of passes.  The pass count and the
    residual (spike flips between the last two passes — 0 on
    convergence) are recorded per launch in ``CompileReport.temporal``.
    Converged output is a true fixed point of the scan arithmetic:
    bit-identical to the sequential kernel for alpha in {0, 1}, and for
    fractional dyadic alpha while products stay exactly representable
    (magnitude bits + T <= 24); outside that window it agrees to f32
    rounding with at most ``residual`` spike flips (0 when converged).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ...kernels.lif_parallel_scan import lif_parallel_scan
from .serial_runtime import ell_gather

#: resolution modes, in preference order
TEMPORAL_MODES = ("alpha0", "count", "iterative")


def choose_temporal_mode(
    alpha: float, v_th: float, *, nonneg_weights: bool
) -> str:
    """Pick the cheapest exact reset-resolution mode a layer admits."""
    if alpha == 0.0:
        return "alpha0"
    if (
        alpha == 1.0
        and nonneg_weights
        and float(v_th).is_integer()
        and v_th >= 1.0
    ):
        return "count"
    return "iterative"


@dataclasses.dataclass(frozen=True)
class TemporalReport:
    """Per-launch record of the temporal paradigm's reset resolution.

    Keys of ``modes`` / ``iterations`` / ``residual`` are population
    indices (declared order).  Exact modes always report one pass and
    zero residual; iterative populations report the fixed-point pass
    count and the number of spike flips between the final two passes —
    the documented bound is ``residual == 0`` whenever ``iterations <
    max_iters`` (the loop only stops early on convergence).
    """

    split: Tuple[int, int, int]          # (pre, serial-block, post) pops
    modes: Dict[int, str]
    iterations: Dict[int, int]
    residual: Dict[int, int]
    max_iters: int

    def as_dict(self) -> dict:
        return {
            "split": list(self.split),
            "modes": {str(k): v for k, v in self.modes.items()},
            "iterations": {str(k): v for k, v in self.iterations.items()},
            "residual": {str(k): v for k, v in self.residual.items()},
            "max_iters": self.max_iters,
        }


# ---------------------------------------------------------------------------
# whole-train projection


def _delayed_sum(y: jnp.ndarray, steps: int) -> jnp.ndarray:
    """Sum per-delay contributions y (d_slots, T, B, N) shifted by their
    delay into one (T, B, N) input-current train.  Slot 0 is the unused
    zero row (delays start at 1), so it never contributes."""
    d_slots = y.shape[0]
    out = jnp.zeros(y.shape[1:], y.dtype)
    for d in range(1, d_slots):
        if d >= steps:
            break
        pad = jnp.zeros((d,) + y.shape[2:], y.dtype)
        out = out + jnp.concatenate([pad, y[d, : steps - d]], axis=0)
    return out


def temporal_project_dense(w_dense: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Whole-train dense projection: x (T, B, S) f32 spikes through the
    delay-stacked weights w (d_slots, S, N) -> currents (T, B, N)."""
    y = jnp.einsum("tbs,dsn->dtbn", x, w_dense)
    return _delayed_sum(y, x.shape[0])


def temporal_project_sparse(
    ell_val: jnp.ndarray,
    ell_idx: jnp.ndarray,
    x: jnp.ndarray,
    *,
    delay_range: int,
    n_target: int,
) -> jnp.ndarray:
    """Whole-train ELL projection: the per-step gather-accumulate
    vmapped over time, then the same shift-and-sum as the dense form."""
    steps = x.shape[0]
    d_slots = delay_range + 1
    gat = jax.vmap(
        lambda xt: ell_gather(ell_val, ell_idx, xt.T)
    )(x)                                               # (T, d_slots*N, B)
    y = gat.reshape(steps, d_slots, n_target, -1)
    y = jnp.transpose(y, (1, 0, 3, 2))                 # (d_slots, T, B, N)
    return _delayed_sum(y, steps)


# ---------------------------------------------------------------------------
# reset resolution


def _temporal_alpha0(i_full: jnp.ndarray, v_th: float) -> jnp.ndarray:
    vth = jnp.float32(v_th)
    f0 = (i_full >= vth).astype(jnp.float32)           # step image of z=0
    f1 = (i_full - vth >= vth).astype(jnp.float32)     # step image of z=1

    def compose(left, right):                          # right after left
        l0, l1 = left
        r0, r1 = right
        return r0 + l0 * (r1 - r0), r0 + l1 * (r1 - r0)

    z, _ = jax.lax.associative_scan(compose, (f0, f1), axis=0)
    return z                                           # composed chain at z=0


def _temporal_count(i_full: jnp.ndarray, v_th: float) -> jnp.ndarray:
    steps = i_full.shape[0]
    vthi = jnp.int32(round(v_th))
    u = jnp.cumsum(i_full.astype(jnp.int32), axis=0)
    k = u // vthi
    t_idx = jnp.arange(steps, dtype=jnp.int32).reshape(
        (steps,) + (1,) * (i_full.ndim - 1)
    )
    m = jax.lax.associative_scan(jnp.minimum, k - t_idx, axis=0)
    n = t_idx + jnp.minimum(m, 1)                      # cumulative spikes
    nprev = jnp.concatenate([jnp.zeros_like(n[:1]), n[:-1]], axis=0)
    return (n - nprev).astype(jnp.float32)


def _temporal_iterative(
    i_full: jnp.ndarray,
    v_th: float,
    alpha: float,
    max_iters: int,
    interpret: bool | None,
):
    steps = i_full.shape[0]
    flat = i_full.reshape(steps, -1)
    vth = jnp.float32(v_th)

    def one_pass(z):
        zprev = jnp.concatenate([jnp.zeros_like(z[:1]), z[:-1]], axis=0)
        v = lif_parallel_scan(flat - zprev * vth, alpha=alpha,
                              interpret=interpret)
        return (v >= vth).astype(jnp.float32)

    def cond(state):
        k, _, diff = state
        return (diff > 0) & (k < max_iters)

    def body(state):
        k, z, _ = state
        z_new = one_pass(z)
        diff = jnp.sum((z_new != z).astype(jnp.int32))
        return k + 1, z_new, diff

    init = (jnp.int32(0), jnp.zeros_like(flat), jnp.int32(1))
    iters, z, residual = jax.lax.while_loop(cond, body, init)
    # `residual` is the flip count of the final pass: 0 on convergence,
    # positive only when the max_iters cap cut the loop short.
    return z.reshape(i_full.shape), iters, residual


def temporal_lif(
    i_full: jnp.ndarray,
    *,
    alpha: float,
    v_th: float,
    mode: str,
    max_iters: int | None = None,
    interpret: bool | None = None,
):
    """Resolve the spike train for a whole (T, B, N) current train.

    Returns ``(z, iterations, residual)`` with ``z`` f32 0/1 of the same
    shape and two int32 scalars (always ``(1, 0)`` in the exact modes).
    """
    if mode == "alpha0":
        z = _temporal_alpha0(i_full, v_th)
        return z, jnp.int32(1), jnp.int32(0)
    if mode == "count":
        z = _temporal_count(i_full, v_th)
        return z, jnp.int32(1), jnp.int32(0)
    if mode != "iterative":
        raise ValueError(f"unknown temporal mode {mode!r}")
    cap = int(max_iters) if max_iters else i_full.shape[0] + 1
    return _temporal_iterative(i_full, v_th, alpha, cap, interpret)


def temporal_step(
    w_dense: jnp.ndarray,
    spikes: jnp.ndarray,
    *,
    alpha: float,
    v_th: float,
    mode: str | None = None,
    max_iters: int | None = None,
    interpret: bool | None = None,
):
    """One projection + its LIF over the whole train — the temporal
    analogue of the serial/parallel runtimes' per-step ``*_step``.

    ``spikes`` is (T, B, S) f32; ``w_dense`` the (d_slots, S, N)
    delay-stacked weights (``dense_serial_weights`` layout).  When
    ``mode`` is None the cheapest admissible mode is chosen from the
    concrete weights.  Returns ``(z, iterations, residual)``.
    """
    if mode is None:
        mode = choose_temporal_mode(
            alpha, v_th, nonneg_weights=bool((w_dense >= 0).all())
        )
    i_full = temporal_project_dense(w_dense, spikes)
    return temporal_lif(
        i_full, alpha=alpha, v_th=v_th, mode=mode, max_iters=max_iters,
        interpret=interpret,
    )
