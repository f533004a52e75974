"""The selective scan's Pallas kernel pair (``ops/selscan.py``), interpreted
on the CPU, against XLA's loops over the same numbers and against the
slot-by-slot recurrence: ``y`` and the five cotangents, with history
boundaries where the kernel's strips and grid steps meet. Rows of 64 slots
in grid steps of 16 (strips of 8), 256 channels on a state of 16 unless a
case says otherwise."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import selscan as ss
from predictionio_tpu.ops import shortconv as sc
from predictionio_tpu.testing import phi4flash_reference as ref

L, C, N = 64, 256, 16
NAMES = ("y", "c", "Delta", "A_log", "B", "C")


@pytest.fixture(autouse=True)
def small_steps(monkeypatch):
    monkeypatch.setattr(ss, "_STRIP", 8)
    monkeypatch.setattr(ss, "_WALK", 16)
    yield
    ss._forward.clear_cache()  # traced at these sizes
    ss._backward.clear_cache()


def rel(got, want):
    got, want = (np.ravel(np.asarray(a, np.float64)) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _runs(*lengths):
    """Segment ids of histories of these lengths, one after another."""
    return np.concatenate([np.full(n, i + 1) for i, n in enumerate(lengths)])


BOUNDARIES = {
    "on a strip's first slot": _runs(8, 16, 40),
    "on a strip's last slot": _runs(7, 16, 41),
    "on a grid step's edge": _runs(16, 16, 32),
    "several inside one strip": _runs(17, 1, 3, 2, 41),
    "a history spanning three grid steps": _runs(10, 50, 4),
    "a row of one history": _runs(64),
    "a padded tail (id 0)": np.concatenate([_runs(20, 30), np.zeros(14, int)]),
    "every slot its own history": _runs(*[1] * 64),
}


def _inputs(seed, segs, channels=C, state=N, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    segs = np.atleast_2d(np.asarray(segs, np.int32))
    rows, length = segs.shape
    x = jnp.asarray(rng.normal(size=(rows, length, channels)), dtype)
    b, c = (rng.normal(size=(rows, length, state)).astype(np.float32) for _ in range(2))
    # decays from nearly none to a state forgotten within a few slots
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), size=x.shape)).astype(np.float32)
    a_log = np.log(rng.uniform(1.0, 16.0, size=(channels, state))).astype(np.float32)
    weight = rng.normal(size=x.shape).astype(np.float32)
    return (x, dt, a_log, b, c, segs), weight


def _weighted(scan):
    """``scan`` -> ``y`` and every gradient of a weighted sum of it."""
    def total(x, dt, a_log, b, c, segs, weight):
        y = scan(x, dt, a_log, b, c, segs)
        return jnp.sum(y * weight), y

    def run(inputs, weight):
        (_, y), grads = jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            *inputs, weight)
        return (y,) + grads

    return jax.jit(run)


@functools.cache
def _form(interpret, low=False):
    """The kernel (interpreted) or XLA's loops, jitted once for all cases of
    one shape; ``low``: the control build's bfloat16 state and gates."""
    kw = dict(state_dtype=jnp.bfloat16, gate_dtype=jnp.bfloat16) if low else {}
    return _weighted(lambda x, dt, a_log, b, c, segs: ss.selective_scan(
        x, dt, -jnp.exp(a_log), b, c, segs, chunk=8, block=32, interpret=interpret, **kw))


@functools.cache
def _recurrence():
    def rows(x, dt, a_log, b, c, segs):
        return jnp.stack([ref.selective_recurrence(
            x[r].astype(jnp.float32), dt[r], b[r], c[r], a_log, segs[r], block=8)
            for r in range(x.shape[0])])

    return _weighted(rows)


@pytest.mark.parametrize("case", sorted(BOUNDARIES))
def test_kernel_gives_the_xla_forms_and_the_recurrences_output_and_cotangents(case):
    """float32 in another order: 1e-5 on the values; a gradient is a sum over
    every slot of terms of both signs, so 1e-4 (the XLA form's own limits)."""
    segs = np.stack([BOUNDARIES[case], BOUNDARIES["several inside one strip"]])
    assert ss.scan_kind(C, N, L, interpret=True) == "pallas"
    inputs, weight = _inputs(len(case), segs)
    got = _form(True)(inputs, weight)
    with jax.default_matmul_precision("highest"):
        slot_by_slot = _recurrence()(inputs, weight)
    for want in (_form(False)(inputs, weight), slot_by_slot):
        for name, g, w in zip(NAMES, got, want):
            assert g.shape == w.shape and np.isfinite(np.asarray(g)).all(), name
            assert rel(g, w) < (1e-5 if name == "y" else 1e-4), name


@pytest.mark.parametrize("channels,length", [
    pytest.param(2048, 32, id="two tiles of eight lane tiles"),
    pytest.param(384, 32, id="three lane tiles, one tile"),
    pytest.param(128, 128, id="one lane tile, eight grid steps")])
def test_channel_tiles(channels, length):
    """Several channel tiles: what B's and C's cotangents sum over the
    channels is added up over the tiles inside the kernel."""
    inputs, weight = _inputs(channels, _runs(5, length - 12, 7), channels=channels)
    assert ss.scan_kind(channels, N, length, interpret=True) == "pallas"
    for name, g, w in zip(NAMES, _form(True)(inputs, weight), _form(False)(inputs, weight)):
        assert rel(g, w) < (1e-5 if name == "y" else 1e-4), name


def test_bfloat16_input_is_read_as_it_lies_and_its_cotangent_is_bfloat16():
    inputs, weight = _inputs(3, BOUNDARIES["on a grid step's edge"], dtype=jnp.bfloat16)
    got, want = _form(True)(inputs, weight), _form(False)(inputs, weight)
    assert got[1].dtype == jnp.bfloat16 and got[0].dtype == jnp.float32
    assert rel(got[0], want[0]) < 1e-5 and rel(got[1], want[1]) < 1e-2
    for name, g, w in zip(NAMES[2:], got[2:], want[2:]):
        assert rel(g, w) < 1e-4, name


def test_a_fast_decay_overflows_nothing():
    """Every decay is the exponential of a non-positive number: a channel
    that forgets within a slot (dt A = -400 a slot) gives finite values and
    gradients."""
    (x, dt, *rest), weight = _inputs(12, _runs(40, 24))
    got = _form(True)((x, np.full_like(dt, 25.0), *rest), weight)
    assert all(np.isfinite(np.asarray(g)).all() for g in got)


@pytest.mark.parametrize("channels,state,length,state_dtype,gate_dtype,interpret,backend,want", [
    pytest.param(5120, 16, 8192, "float32", "float32", False, "tpu", "pallas", id="the cell's"),
    pytest.param(5120, 16, 8192, "float32", "float32", False, "cpu", "xla", id="the CPU"),
    pytest.param(256, 16, 64, "float32", "float32", True, "cpu", "pallas", id="interpreted"),
    pytest.param(384, 8, 64, "float32", "float32", False, "tpu", "pallas", id="three lane tiles"),
    pytest.param(200, 16, 64, "float32", "float32", True, "tpu", "xla", id="no lane multiple"),
    pytest.param(1152, 16, 64, "float32", "float32", True, "tpu", "xla", id="nine lane tiles"),
    pytest.param(256, 12, 64, "float32", "float32", True, "tpu", "xla", id="a state of 12"),
    pytest.param(256, 16, 60, "float32", "float32", True, "tpu", "xla", id="no whole strips"),
    pytest.param(256, 16, 64, "bfloat16", "float32", True, "tpu", "xla", id="bfloat16 state"),
    pytest.param(256, 16, 64, "float32", "bfloat16", True, "tpu", "xla", id="bfloat16 gates"),
])
def test_scan_kind(monkeypatch, channels, state, length, state_dtype, gate_dtype, interpret,
                   backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ss.scan_kind(channels, state, length, state_dtype, gate_dtype, interpret) == want


def test_the_bfloat16_state_call_takes_the_xla_path():
    """The benchmark's control build (state, Delta and decay in bfloat16)
    is XLA's loops whatever the backend: there is no kernel in its trace and
    its result is the XLA form's to the bit."""
    inputs, weight = _inputs(5, BOUNDARIES["on a strip's last slot"], dtype=jnp.bfloat16)
    assert "pallas_call" not in str(jax.make_jaxpr(_form(True, low=True))(inputs, weight))
    assert "pallas_call" in str(jax.make_jaxpr(_form(True))(inputs, weight))
    got, want = _form(True, low=True)(inputs, weight), _form(False, low=True)(inputs, weight)
    assert all(np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
               for g, w in zip(got, want))
    assert rel(got[0], _form(True)(inputs, weight)[0]) > 1e-3  # and it is another result


def test_the_mixer_on_a_tpu_runs_the_kernel_and_gives_what_the_xla_form_gives(monkeypatch):
    """``mamba1`` as the step calls it, with the backend answered as a TPU
    and the kernels handed to Pallas's interpreter: the mixer's output, what
    it hands on and every gradient against the CPU's XLA forms."""
    rng = np.random.default_rng(7)
    d, inner, state, rank = 16, 128, 8, 4
    w = lambda *shape: jnp.asarray(0.3 * rng.normal(size=shape), jnp.float32)  # noqa: E731
    p = {"w_in": w(d, 2 * inner), "conv_w": w(4, inner), "conv_b": w(inner),
         "w_x": w(inner, rank + 2 * state), "w_dt": w(rank, inner), "dt_bias": w(inner),
         "A_log": jnp.log(jnp.asarray(rng.uniform(1, 16, (inner, state)), jnp.float32)),
         "D": 1 + w(inner), "w_out": w(inner, d)}
    x = jnp.asarray(rng.normal(size=(2, L, d)), jnp.float32)
    seg = jnp.asarray(np.stack([BOUNDARIES["several inside one strip"],
                                BOUNDARIES["a padded tail (id 0)"]]), jnp.int32)

    def loss(p, x):
        out, ran = ss.mamba1(p, x, seg, state=state, dt_rank=rank, chunk=8)
        return jnp.sum(out * out) + jnp.sum(ran["m"]), (out, ran["y"])

    def traced(loss=loss):
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1), has_aux=True))(p, x))

    want = jax.grad(loss, argnums=(0, 1), has_aux=True)(p, x)
    assert "pallas_call" not in traced()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for module in (ss, sc):
        monkeypatch.setattr(module, "_params", lambda interpret, real=module._params: real(True))
    on_tpu = lambda p, x: loss(p, x)  # noqa: E731  (a trace of its own)
    assert traced(on_tpu).count("pallas_call") >= 4  # the convolution's and the scan's, both ways
    got = jax.grad(on_tpu, argnums=(0, 1), has_aux=True)(p, x)
    for g, w_ in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert rel(g, w_) < 1e-4
