"""The state-space scan's Pallas kernel pair (``ops/ssd.py``), interpreted on
the CPU, against XLA's batch products over the same numbers and against the
slot-by-slot recurrence: ``y`` and the five cotangents, with history
boundaries where the kernel's chunks meet. Rows of 64 slots in chunks of 16,
4 heads of 8 on a state of 16, two heads a grid step and a lane tile (of 16
lanes here), unless a case says otherwise."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb
from predictionio_tpu.ops import shortconv as sc
from predictionio_tpu.ops import ssd
from predictionio_tpu.testing import granite4h_reference as ref

L, H, P, N, CHUNK = 64, 4, 8, 16, 16
NAMES = ("y", "u", "dt", "A_log", "B", "C")


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(ssd, "_LANES", 16)
    monkeypatch.setattr(ssd, "_HEADS", 2)
    yield
    ssd._forward.clear_cache()  # traced at these sizes
    ssd._backward.clear_cache()


def rel(got, want):
    got, want = (np.ravel(np.asarray(a, np.float64)) for a in (got, want))
    return float(np.linalg.norm(got - want) / (np.linalg.norm(want) + 1e-30))


def _runs(*lengths):
    """Segment ids of histories of these lengths, one after another."""
    return np.concatenate([np.full(n, i + 1) for i, n in enumerate(lengths)])


BOUNDARIES = {
    "on a chunk's first slot": _runs(16, 16, 32),
    "on a chunk's last slot": _runs(15, 16, 33),
    "several inside one chunk": _runs(17, 1, 3, 2, 41),
    "one history over three chunks": _runs(10, 50, 4),
    "a row of one history": _runs(64),
    "a padded tail (id 0)": np.concatenate([_runs(20, 30), np.zeros(14, int)]),
    "every slot its own history": _runs(*[1] * 64),
    "a length that is no whole chunk": _runs(20, 30, 6),
}


def _inputs(seed, segs, heads=H, width=P, state=N, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    segs = np.atleast_2d(np.asarray(segs, np.int32))
    rows, length = segs.shape
    u = jnp.asarray(rng.normal(size=(rows, length, heads, width)), dtype)
    b, c = (jnp.asarray(rng.normal(size=(rows, length, state)), dtype) for _ in range(2))
    # decays from nearly none to a state forgotten within a few slots
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(2.0), size=u.shape[:3])).astype(np.float32)
    a_log = np.log(rng.uniform(1.0, 16.0, size=(heads,))).astype(np.float32)
    weight = rng.normal(size=u.shape).astype(np.float32)
    return (u, dt, a_log, b, c, segs), weight


def _weighted(scan):
    """``scan`` -> ``y`` and every gradient of a weighted sum of it."""
    def total(u, dt, a_log, b, c, segs, weight):
        y = scan(u, dt, a_log, b, c, segs)
        return jnp.sum(y * weight), y

    def run(inputs, weight):
        (_, y), grads = jax.value_and_grad(total, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            *inputs, weight)
        return (y,) + grads

    return jax.jit(run)


@functools.cache
def _form(interpret, low=False, compute=None, chunk=CHUNK):
    """The kernel (interpreted) or XLA's batch products, jitted once for all
    cases of one shape; ``low``: the control build's bfloat16 state and
    gates; ``compute``: the products' input dtype."""
    kw = dict(state_dtype=jnp.bfloat16, gate_dtype=jnp.bfloat16) if low else {}
    if compute:
        kw["compute_dtype"] = jnp.dtype(compute)
    return _weighted(lambda u, dt, a_log, b, c, segs: ssd.ssd_scan(
        u, dt, -jnp.exp(a_log), b, c, segs, chunk=chunk, interpret=interpret, **kw))


@functools.cache
def _recurrence():
    def rows(u, dt, a_log, b, c, segs):
        return jnp.stack([ref.ssd_recurrence(
            u[r].astype(jnp.float32), b[r], c[r], dt[r], a_log, segs[r], block=8)
            for r in range(u.shape[0])])

    return _weighted(rows)


@pytest.mark.parametrize("case", sorted(BOUNDARIES))
def test_kernel_gives_the_xla_forms_and_the_recurrences_output_and_cotangents(case):
    """float32 in another order: 1e-5 on the values; a gradient is a sum over
    every slot of terms of both signs, so 1e-4 (the XLA form's own limits)."""
    other = BOUNDARIES["several inside one chunk"][:len(BOUNDARIES[case])]
    segs = np.stack([BOUNDARIES[case], other])
    assert ssd.scan_kind(H, P, N, segs.shape[1], CHUNK, interpret=True) == "pallas"
    inputs, weight = _inputs(len(case), segs)
    got = _form(True)(inputs, weight)
    with jax.default_matmul_precision("highest"):
        slot_by_slot = _recurrence()(inputs, weight)
    for want in (_form(False)(inputs, weight), slot_by_slot):
        for name, g, w in zip(NAMES, got, want):
            assert g.shape == w.shape and np.isfinite(np.asarray(g)).all(), name
            assert rel(g, w) < (1e-5 if name == "y" else 1e-4), name


# -- groups of heads that share B and C ---------------------------------------
@functools.cache
def _grouped_form(interpret, groups, chunk=CHUNK):
    return _weighted(lambda u, dt, a_log, b, c, segs: ssd.ssd_scan(
        u, dt, -jnp.exp(a_log), b, c, segs, chunk=chunk, groups=groups, interpret=interpret))


@functools.cache
def _grouped_recurrence(groups):
    from predictionio_tpu.testing import nemotronh_reference as grouped

    def rows(u, dt, a_log, b, c, segs):
        split = lambda t, r: t[r].reshape(t.shape[1], groups, -1)  # noqa: E731
        return jnp.stack([grouped.ssd_recurrence(
            u[r].astype(jnp.float32), split(b, r), split(c, r), dt[r], a_log, segs[r], block=8)
            for r in range(u.shape[0])])

    return _weighted(rows)


@pytest.mark.parametrize("case", ["on a chunk's first slot", "on a chunk's last slot",
                                  "several inside one chunk", "a length that is no whole chunk"])
@pytest.mark.parametrize("heads,width,groups", [
    pytest.param(8, P, 4, id="a grid step a group"),
    pytest.param(8, P, 2, id="two grid steps a group"),
    pytest.param(4, 16, 4, id="a head a group, a grid step of one head"),
    pytest.param(4, P, 1, id="one group, said")])
def test_grouped_heads_read_their_groups_b_and_c_in_both_forms(case, heads, width, groups):
    """Head i on ``B``, ``C`` of group ``i // (H / G)``: the kernel
    (interpreted; a grid step's heads share a group, whose columns it is
    handed by block index, and whose cotangents it sums over the group's grid
    steps alone) and XLA's batch products (a ``vmap`` over the groups) against
    the slot-by-slot recurrence, ``y`` and every cotangent, with boundaries on
    a chunk's first, last and inner slots."""
    other = BOUNDARIES["several inside one chunk"][:len(BOUNDARIES[case])]
    segs = np.stack([BOUNDARIES[case], other])
    assert ssd.scan_kind(heads, width, N, segs.shape[1], CHUNK, interpret=True,
                         groups=groups) == "pallas"
    assert ssd._tile(heads, width, groups) == min(2, heads // groups)
    inputs, weight = _inputs(len(case) + groups, segs, heads=heads, width=width, state=groups * N)
    with jax.default_matmul_precision("highest"):
        want = _grouped_recurrence(groups)(inputs, weight)
    for interpret in (True, False):
        got = _grouped_form(interpret, groups)(inputs, weight)
        for name, g, w in zip(NAMES, got, want):
            assert g.shape == w.shape and np.isfinite(np.asarray(g)).all(), name
            assert rel(g, w) < (1e-5 if name == "y" else 1e-4), (name, interpret)
    if groups > 1:  # and not what one group's B and C for all heads gives
        inputs_one = inputs[:3] + tuple(jnp.tile(t[..., :N], (1, 1, groups)) for t in inputs[3:5]) + inputs[5:]
        assert rel(_grouped_form(False, groups)(inputs_one, weight)[0], want[0]) > 0.3


def test_groups_that_split_no_grid_step_take_the_xla_form():
    """Three heads a group and lane tiles of two: no grid step's heads would
    share a group, so the call is XLA's."""
    assert ssd._tile(6, P, 2) == 0 and ssd._tile(6, P, 3) == 2
    assert ssd.scan_kind(6, 16, N, L, CHUNK, interpret=True, groups=2) == "pallas"  # a head a tile
    assert ssd.scan_kind(6, P, N, L, CHUNK, interpret=True, groups=2) == "xla"  # half a lane tile
    assert ssd.scan_kind(6, P, N, L, CHUNK, interpret=True, groups=4) == "xla"  # no equal groups


@pytest.mark.parametrize("heads,width,a_step", [
    pytest.param(8, 8, 2, id="four grid steps of two heads"),
    pytest.param(4, 8, 4, id="four heads in one grid step, two lane tiles"),
    pytest.param(6, 8, 4, id="six heads, three grid steps"),
    pytest.param(2, 16, 2, id="a head a lane tile"),
    pytest.param(8, 4, 4, id="four heads a lane tile")])
def test_tiles_of_heads(monkeypatch, heads, width, a_step):
    """Several grid steps a chunk: what B's and C's cotangents sum over the
    heads is added up over the steps inside the kernel; the states of all
    heads stay in VMEM."""
    monkeypatch.setattr(ssd, "_HEADS", a_step)
    inputs, weight = _inputs(heads, _runs(5, 40, 19), heads=heads, width=width)
    assert ssd.scan_kind(heads, width, N, L, CHUNK, interpret=True) == "pallas"
    got, want = _form(True)(inputs, weight), _form(False)(inputs, weight)
    ssd._forward.clear_cache(), ssd._backward.clear_cache()
    for name, g, w in zip(NAMES, got, want):
        assert rel(g, w) < (1e-5 if name == "y" else 1e-4), name


@pytest.mark.parametrize("chunk", [32, 48])
def test_chunks_of_several_lane_tiles(chunk):
    """A chunk of two or three lane tiles (the cell's: 256 slots, two): the
    chunk is no part of the result."""
    segs = np.stack([_runs(17, 1, 3, 2, 41, 32), _runs(10, 80, 6)])
    assert ssd.scan_kind(H, P, N, 96, chunk, interpret=True) == "pallas"
    inputs, weight = _inputs(chunk, segs)
    got, want = _form(True, chunk=chunk)(inputs, weight), _form(False)(inputs, weight)
    for name, g, w in zip(NAMES, got, want):
        assert rel(g, w) < (1e-5 if name == "y" else 1e-4), name


def test_bfloat16_inputs_are_read_as_they_lie_and_their_cotangents_are_bfloat16():
    """``u``, ``B``, ``C`` in bfloat16 with bfloat16 products, as the step
    hands them over: the kernel rounds where the XLA form rounds (``dt u``,
    the score-times-decay matrix, what a chunk writes to the state)."""
    inputs, weight = _inputs(3, BOUNDARIES["on a chunk's last slot"], dtype=jnp.bfloat16)
    got, want = (_form(i, compute="bfloat16")(inputs, weight) for i in (True, False))
    assert got[0].dtype == jnp.float32 and got[2].dtype == got[3].dtype == jnp.float32
    assert all(got[i].dtype == jnp.bfloat16 for i in (1, 4, 5))
    assert rel(got[0], want[0]) < 1e-5
    for name, g, w in zip(NAMES[1:], got[1:], want[1:]):
        assert rel(g, w) < 2e-2, name  # the cotangents are rounded to bfloat16 on both sides
    exact = _form(True)(inputs, weight)  # and float32 products on the same inputs are another result
    assert 1e-4 < rel(got[0], exact[0]) < 2e-2


def test_a_fast_decay_overflows_nothing():
    """Every decay is the exponential of a masked difference: a head that
    forgets within a slot (dt A = -400 a slot) gives finite values and
    gradients, and the recurrence's."""
    (u, dt, *rest), weight = _inputs(12, _runs(40, 24))
    inputs = (u, np.full_like(dt, 25.0), *rest)
    got = _form(True)(inputs, weight)
    assert all(np.isfinite(np.asarray(g)).all() for g in got)
    assert rel(got[0], _form(False)(inputs, weight)[0]) < 1e-5


@pytest.mark.parametrize(
    "heads,width,state,length,chunk,state_dtype,gate_dtype,interpret,backend,want", [
        pytest.param(64, 64, 128, 8192, 256, "float32", "float32", False, "tpu", "pallas",
                     id="the cell's"),
        pytest.param(64, 64, 128, 8192, 256, "float32", "float32", False, "cpu", "xla",
                     id="the CPU"),
        pytest.param(64, 64, 128, 8192, 256, "float32", "float32", True, "cpu", "pallas",
                     id="interpreted"),
        pytest.param(64, 64, 128, 8000, 256, "float32", "float32", False, "tpu", "pallas",
                     id="a row that is padded to whole chunks"),
        pytest.param(24, 128, 256, 4096, 128, "float32", "float32", False, "tpu", "pallas",
                     id="a head a lane tile"),
        pytest.param(8, 16, 16, 64, 16, "float32", "float32", True, "tpu", "xla",
                     id="the tiny configuration's widths"),
        pytest.param(64, 96, 128, 8192, 256, "float32", "float32", False, "tpu", "xla",
                     id="a head that fills no whole part of a lane tile"),
        pytest.param(3, 64, 128, 8192, 256, "float32", "float32", False, "tpu", "xla",
                     id="three heads of 64: no whole lane tiles"),
        pytest.param(64, 64, 64, 8192, 256, "float32", "float32", False, "tpu", "xla",
                     id="a state of 64"),
        pytest.param(64, 64, 128, 8192, 64, "float32", "float32", False, "tpu", "xla",
                     id="chunks of 64"),
        pytest.param(64, 64, 128, 8192, 256, "bfloat16", "float32", True, "tpu", "xla",
                     id="bfloat16 state"),
        pytest.param(64, 64, 128, 8192, 256, "float32", "bfloat16", True, "tpu", "xla",
                     id="bfloat16 gates"),
    ])
def test_scan_kind(monkeypatch, heads, width, state, length, chunk, state_dtype, gate_dtype,
                   interpret, backend, want):
    monkeypatch.setattr(ssd, "_LANES", 128)
    monkeypatch.setattr(ssd, "_HEADS", 8)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ssd.scan_kind(heads, width, state, length, chunk, state_dtype, gate_dtype,
                         interpret) == want


def test_the_bfloat16_state_call_takes_the_xla_path():
    """The benchmark's control build (state, Delta and the running sums in
    bfloat16) is XLA's batch products whatever the backend: there is no
    kernel in its trace and its result is the XLA form's to the bit."""
    inputs, weight = _inputs(5, BOUNDARIES["on a chunk's last slot"], dtype=jnp.bfloat16)
    assert "pallas_call" not in str(jax.make_jaxpr(_form(True, low=True))(inputs, weight))
    assert "pallas_call" in str(jax.make_jaxpr(_form(True))(inputs, weight))
    got, want = _form(True, low=True)(inputs, weight), _form(False, low=True)(inputs, weight)
    assert all(np.array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))
               for g, w in zip(got, want))
    assert rel(got[0], _form(True)(inputs, weight)[0]) > 1e-3  # and it is another result


def test_the_control_builds_configuration_asks_for_the_xla_form(monkeypatch):
    """``--control bf16_state`` sets the backbone's ``state_dtype`` and
    ``gate_dtype``: on a TPU at the cell's widths the sound build's counter
    reads ``pallas`` and the control's ``xla``."""
    import dataclasses

    monkeypatch.setattr(ssd, "_LANES", 128)
    monkeypatch.setattr(ssd, "_HEADS", 8)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = bb.BackboneConfig.load("granite4h-micro-vp8")
    control = dataclasses.replace(cfg, state_dtype="bfloat16", gate_dtype="bfloat16")
    shapes = {"w_in": (2048, 8448), "conv_w": (4, 4352)}
    widths = dict(heads=64, head_dim=64, state=128, chunk=256)
    assert ssd.forms(shapes, 8192, **widths)["ssd_scan"] == "pallas"
    assert ssd.forms(shapes, 8192, **widths, state_dtype="bfloat16",
                     gate_dtype="bfloat16")["ssd_scan"] == "xla"
    assert bb.mechanisms(cfg, 8192)["ssd_scan"] == "pallas"
    assert bb.mechanisms(control, 8192)["ssd_scan"] == "xla"


def test_the_mixer_on_a_tpu_runs_the_kernel_and_gives_what_the_xla_form_gives(monkeypatch):
    """``mamba2`` as the step calls it, with the backend answered as a TPU
    and the kernels handed to Pallas's interpreter: the mixer's output, the
    scan's and every gradient against the CPU's XLA forms."""
    rng = np.random.default_rng(7)
    d, heads, width, state = 16, 16, 8, 64
    inner = heads * width
    w = lambda *shape: jnp.asarray(0.3 * rng.normal(size=shape), jnp.float32)  # noqa: E731
    p = {"w_in": w(d, 2 * inner + 2 * state), "w_dt": w(d, heads),
         "conv_w": w(4, inner + 2 * state), "conv_b": w(inner + 2 * state),
         "A_log": jnp.log(jnp.asarray(rng.uniform(1, 16, heads), jnp.float32)),
         "dt_bias": w(heads), "D": 1 + w(heads), "norm": 1 + w(inner), "w_out": w(inner, d)}
    x = jnp.asarray(rng.normal(size=(2, L, d)), jnp.float32)
    seg = jnp.asarray(np.stack([BOUNDARIES["several inside one chunk"],
                                BOUNDARIES["a padded tail (id 0)"]]), jnp.int32)

    def loss(p, x):
        out, ran = ssd.mamba2(p, x, seg, heads=heads, head_dim=width, state=state, eps=1e-5,
                              chunk=CHUNK)
        return jnp.sum(out * out), (out, ran["y"])

    def traced(loss=loss):
        return str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1), has_aux=True))(p, x))

    want = jax.grad(loss, argnums=(0, 1), has_aux=True)(p, x)
    assert "pallas_call" not in traced()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for module in (ssd, sc):
        monkeypatch.setattr(module, "_params", lambda interpret, real=module._params: real(True))
    on_tpu = lambda p, x: loss(p, x)  # noqa: E731  (a trace of its own)
    assert traced(on_tpu).count("pallas_call") >= 4  # the convolution's and the scan's, both ways
    got = jax.grad(on_tpu, argnums=(0, 1), has_aux=True)(p, x)
    for g, w_ in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert rel(g, w_) < 1e-4
