"""Pure-jnp oracle of the fixed-point ``iaf_psc_exp`` update.

Every operation is an int32 add, multiply of two factors below 2**16 in
magnitude, shift or mask, so the result is the same bits under XLA on
any backend, in the Pallas kernel and in numpy int64 arithmetic.
"""
from __future__ import annotations

import jax.numpy as jnp

#: Fraction bits of a propagator (``repro.core.layer.PROPAGATOR_BITS``).
P_BITS = 31


def mul_round(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """``floor((x * p + 2**30) / 2**31)`` for int32 ``x`` and a propagator
    ``0 <= p < 2**31``: the high half of the 32 x 32-bit product, rounded
    half up, built from 16-bit limbs so no partial product leaves int32.

    With ``x = xh 2**16 + xl`` and ``p = ph 2**16 + pl`` (``xl``, ``pl``
    in [0, 2**16)), ``x p = xh ph 2**32 + (xh pl + xl ph) 2**16 + xl pl``;
    ``xl pl`` is split once more at 8 bits of ``pl``.  The base-2**16
    digits are carried up; the lowest digit only ever reaches the result
    through its carry.
    """
    ph, pl = p >> 16, p & 0xFFFF
    plh, pll = pl >> 8, pl & 0xFF
    xh = x >> 16                              # arithmetic: in [-2**15, 2**15)
    xl = x & 0xFFFF
    hh = xh * ph
    hl = xh * pl
    lh = xl * ph
    a = xl * plh                              # xl pl = a 2**8 + b
    b = xl * pll
    d0 = ((a & 0xFF) << 8) + b                # digit 0 of xl pl, plus carry
    m1 = ((hl & 0xFFFF) + (lh & 0xFFFF) + (a >> 8) + (d0 >> 16)
          + (1 << (P_BITS - 17)))             # digit 1, with the rounding
    top = hh + (hl >> 16) + (lh >> 16) + (m1 >> 16)
    return top * 2 + ((m1 & 0xFFFF) >> (P_BITS - 16))


def iaf_psc_exp_ref(
    i_in: jnp.ndarray,   # int32 synaptic input of this step, weight units
    v: jnp.ndarray,      # int32 membrane potential
    i_syn: jnp.ndarray,  # int32 synaptic current
    r: jnp.ndarray,      # int32 refractory steps left
    *,
    p22: int, p21: int, p11: int, v_drive: int,
    v_th: int, v_reset: int, t_ref: int, i_bits: int,
):
    """One step of NEST's ``iaf_psc_exp`` in fixed point; elementwise over
    maps of any shape.  Returns ``(v', i_syn', r', z)``, int32 (``z`` is
    0/1)::

        if r == 0:  v <- mul(p22, v) + mul(p21, i_syn) + v_drive
        else:       r <- r - 1
        i_syn <- mul(p11, i_syn) + (i_in << i_bits)
        z = v >= v_th;  where z: v <- v_reset, r <- t_ref
    """
    free = r == 0
    v_free = mul_round(v, p22) + mul_round(i_syn, p21) + v_drive
    v = jnp.where(free, v_free, v)
    r = jnp.where(free, r, r - 1)
    i_syn = mul_round(i_syn, p11) + (i_in << i_bits)
    spike = v >= v_th
    v = jnp.where(spike, v_reset, v)
    r = jnp.where(spike, t_ref, r)
    return v, i_syn, r, spike.astype(jnp.int32)
