from .ops import iaf_layout, iaf_psc_exp, iaf_psc_exp_ref, mul_round
