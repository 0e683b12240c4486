"""The fixed-point ``iaf_psc_exp`` neuron: kernel, jnp reference and
numpy int64 agree bit for bit; the executor runs it against the
program's own reference; the temporal path refuses it."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import SwitchingCompiler
from repro.core.layer import (
    IafPscExpParams,
    LIFParams,
    Population,
    SNNNetwork,
    SparseProjection,
)
from repro.core.runtime import network_executable
from repro.core.runtime.reference import iaf_psc_exp_int64, run_graph_reference
from repro.core.switching import CompileReport
from repro.kernels.iaf_psc_exp import iaf_layout, iaf_psc_exp, iaf_psc_exp_ref, mul_round

PARAMS = IafPscExpParams(weight_pa=4.0, i_e=560.0)
CONSTS = PARAMS.fixed()


def test_fixed_constants_follow_nest_propagators():
    p22, p21, p11, p20 = PARAMS.propagators()
    assert CONSTS.p22 == int(np.floor(p22 * 2**31 + 0.5))
    assert CONSTS.p11 == int(np.floor(p11 * 2**31 + 0.5))
    assert abs(CONSTS.p21 / 2**31 - p21 * 4.0 * 2**4) < 2**-31
    assert CONSTS.v_th == 15 * 2**16 and CONSTS.v_reset == 0
    assert CONSTS.t_ref == 20 and CONSTS.i_bits == 12
    assert abs(CONSTS.v_drive - p20 * 560.0 * 2**16) <= 0.5
    v0 = IafPscExpParams(v0=np.array([-65.0, -50.0, -58.3])).v0_fixed(3)
    assert v0.tolist() == [0, 15 * 2**16, int(np.floor(6.7 * 2**16 + 0.5))]


@pytest.mark.parametrize("p", [0, 1, 2**30, 2**31 - 1, CONSTS.p22, CONSTS.p21,
                               CONSTS.p11, 12345, 987654321])
def test_mul_round_is_the_rounded_high_half(p):
    rng = np.random.default_rng(p % 1000)
    x = np.concatenate([rng.integers(-2**31, 2**31, 20000),
                        [-2**31, 2**31 - 1, 0, -1, 1, 65535, -65536]])
    x = x.astype(np.int32)
    want = (x.astype(np.int64) * p + 2**30) >> 31
    got = np.asarray(jax.jit(lambda a: mul_round(a, p))(jnp.asarray(x)))
    np.testing.assert_array_equal(got, want)


def _states(rng, shape):
    """Random states over the whole int32 range the update keeps, with
    every branch taken: refractory and free, over and under threshold,
    and the extremes of potential and current."""
    v = rng.integers(-2**30, 2**30, shape)
    v.flat[:4] = [CONSTS.v_th, CONSTS.v_th - 1, -2**30, 2**30]
    i = rng.integers(-2**29, 2**29, shape)
    i.flat[4:8] = [2**29, -2**29, 0, 1]
    r = np.where(rng.random(shape) < 0.3, rng.integers(1, 21, shape), 0)
    i_in = rng.integers(-3000, 3000, shape)
    return [a.astype(np.int32) for a in (i_in, v, i, r)]


@pytest.mark.parametrize("interpret", [None, True])
def test_kernel_and_reference_equal_numpy_int64(interpret):
    rng = np.random.default_rng(3)
    rows = iaf_layout(1, 1500)
    args = _states(rng, (rows, 128))
    want = iaf_psc_exp_int64(*args, CONSTS)
    got = iaf_psc_exp(*map(jnp.asarray, args), consts=CONSTS,
                      interpret=interpret)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
    # every branch was taken
    z = np.asarray(want[3])
    assert z.any() and not z.all()
    assert (np.asarray(want[2]) == 20).any() and (args[3] > 0).any()


def test_reference_update_branches():
    """Refractory: potential held, count down; free: it evolves; a spike
    resets and starts the count; the bias drives a resting neuron."""
    c = CONSTS
    i_in = np.array([0, 0, 0, 100], np.int32)
    v = np.array([5000, c.v_th + 1, 0, 0], np.int32)
    i = np.zeros(4, np.int32)
    r = np.array([3, 0, 0, 0], np.int32)
    v2, i2, r2, z = (np.asarray(a) for a in iaf_psc_exp_ref(
        i_in, v, i, r, p22=c.p22, p21=c.p21, p11=c.p11, v_drive=c.v_drive,
        v_th=c.v_th, v_reset=c.v_reset, t_ref=c.t_ref, i_bits=c.i_bits))
    assert v2[0] == 5000 and r2[0] == 2 and z[0] == 0
    assert z[1] == 1 and v2[1] == c.v_reset and r2[1] == c.t_ref
    assert v2[2] == c.v_drive and r2[2] == 0
    assert i2[3] == 100 << c.i_bits


def _loop_net(delay_back):
    """x -> A (delay 2), A -> B (delay 3), B -> A back-edge (stored
    delay_back): iaf_psc_exp populations."""
    a = IafPscExpParams(weight_pa=40.0, i_e=300.0,
                        v0=np.linspace(-70, -48, 6))
    b = IafPscExpParams(weight_pa=40.0, i_e=200.0,
                        v0=np.linspace(-66, -51, 4))
    pops = [Population("x", 5), Population("A", 6, a), Population("B", 4, b)]
    rng = np.random.default_rng(7)

    def proj(pre, post, s, t, n, d):
        src = np.sort(rng.integers(0, s, n))
        indptr = np.zeros(s + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=s), out=indptr[1:])
        return SparseProjection(
            n_source=s, n_target=t, indptr=indptr,
            indices=rng.integers(0, t, n), values=rng.integers(10, 42, n) * 1.0,
            delay_values=np.full(n, d), delay_range=max(1, d),
            name=f"{pre}->{post}", pre=pre, post=post)

    return SNNNetwork(populations=pops, projections=[
        proj("x", "A", 5, 6, 20, 2), proj("A", "B", 6, 4, 18, 3),
        proj("B", "A", 4, 6, 12, delay_back)])


@pytest.mark.parametrize("policy", ["serial", "parallel"])
def test_iaf_network_matches_the_program_reference(policy):
    net = _loop_net(0)
    report = CompileReport([SwitchingCompiler(policy).compile_layer(e)
                            for e in net.layers])
    exe = network_executable(net, report)
    x = (np.random.default_rng(1).random((60, 3, 5)) < 0.3).astype(np.float32)
    want = run_graph_reference(net, x)
    forms = ("event", "sparse", "dense", "spike") if policy == "serial" \
        else ("auto",)
    for form in forms:
        got = exe.run(x, serial_form=form)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
    assert sum(w.sum() for w in want) > 0


def test_temporal_path_refuses_iaf_psc_exp():
    net = _loop_net(1)
    report = SwitchingCompiler("serial").compile_network(net)
    exe = network_executable(net, report)
    x = np.zeros((8, 1, 5), np.float32)
    with pytest.raises(ValueError, match="iaf_psc_exp"):
        exe.run(x, temporal=True)
    with pytest.raises(ValueError, match="iaf_psc_exp"):
        exe.temporal_forms(1, 8)


def test_population_lif_may_be_either_model():
    net = _loop_net(1)
    assert isinstance(net.population_lif(1), IafPscExpParams)
    lif = LIFParams(alpha=0.5, v_th=64.0)
    assert IafPscExpParams() == IafPscExpParams(v0=np.ones(3))
    assert hash(IafPscExpParams()) == hash(IafPscExpParams(v0=np.ones(3)))
    assert lif != IafPscExpParams()


#: Potjans & Diesmann (2014) population sizes, L2/3E ... L6I, and the DC
#: background each receives (K_ext x 8 Hz x 87.8 pA x 0.5 ms).
PD14 = [(20683, 1600), (5834, 1500), (21915, 2100), (5479, 1900),
        (4850, 2000), (1065, 1900), (14395, 2900), (2948, 2100)]


@pytest.mark.parametrize("batch,n,k_ext", [(1, n, k) for n, k in PD14]
                         + [(3, 1065, 1900), (5, 301, 2100)])
def test_kernel_first_step_from_v0_equals_numpy_int64(batch, n, k_ext):
    """One step of the kernel body from a population's initial state
    (NEST's V0 ~ N(-58, 10) mV, no current, not refractory) with this
    step's synaptic input, laid out as the executor lays it out."""
    rng = np.random.default_rng(n)
    params = IafPscExpParams(weight_pa=87.8, i_e=k_ext * 8 * 87.8 * 0.5e-3,
                             v0=rng.normal(-58.0, 10.0, n))
    consts = params.fixed()
    rows = iaf_layout(batch, n)

    def lay(a):
        flat = np.zeros(rows * 128, np.int32)
        flat[: a.size] = a.reshape(-1)
        return flat.reshape(rows, 128)

    v = lay(np.tile(params.v0_fixed(n), (batch, 1)))
    i_in = lay(rng.integers(-60, 120, (batch, n)))
    zero = np.zeros((rows, 128), np.int32)
    args = (i_in, v, zero, zero)
    want = iaf_psc_exp_int64(*args, consts)
    got = iaf_psc_exp(*map(jnp.asarray, args), consts=consts, interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)
    z = np.asarray(want[3]).reshape(-1)[: batch * n]
    assert z.any() and not z.all()        # V0 straddles the threshold


@pytest.mark.parametrize("batch,delay_back", [(1, 0), (3, 2)])
@pytest.mark.parametrize("form", ["event", "sparse", "dense", "spike"])
def test_iaf_network_each_serial_form(form, batch, delay_back):
    net = _loop_net(delay_back)
    report = SwitchingCompiler("serial").compile_network(net)
    exe = network_executable(net, report)
    x = (np.random.default_rng(batch).random((50, batch, 5)) < 0.3
         ).astype(np.float32)
    want = run_graph_reference(net, x)
    got = exe.run(x, serial_form=form)
    assert report.serial_forms[("fused", batch)] == (form,) * 3
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert all(w.sum() > 0 for w in want)


@pytest.mark.parametrize("batch", [1, 3])
def test_iaf_network_parallel_with_a_delayed_back_edge(batch):
    net = _loop_net(2)
    report = SwitchingCompiler("parallel").compile_network(net)
    exe = network_executable(net, report)
    x = (np.random.default_rng(batch).random((50, batch, 5)) < 0.3
         ).astype(np.float32)
    want = run_graph_reference(net, x)
    for g, w in zip(exe.run(x), want):
        np.testing.assert_array_equal(g, w)
    assert all(w.sum() > 0 for w in want)
