"""The short convolution's Pallas kernel (``ops/shortconv.py``), interpreted
on the CPU, against the XLA form of the same chain: forward and every
cotangent, for each piece of the chain the three mixers ask for, with history
boundaries where the tiles meet. Rows of 64 slots in tiles of 16 slots and
128 lanes, so every case crosses three halos and two channel tiles."""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import shortconv as sc

L, C = 64, 256


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(sc, "_TILE", 16 * 128)
    monkeypatch.setattr(sc, "_WIDEST", 128)


def _segs(where: str) -> np.ndarray:
    """Two rows of history ids. ``first_slot``: a history starts on a tile's
    first slot (16, 48); ``in_halo``: one, two and three slots before it
    (31, 46, 13), and a history of one slot; ``none``: one history a row;
    ``mixed``: both, and two boundaries inside one tile."""
    starts = {"first_slot": ([16, 48], [32]), "in_halo": ([31, 46], [13, 14, 61]),
              "none": ([], []), "mixed": ([16, 31, 35, 37], [5, 30, 48, 63])}[where]
    return np.stack([1 + np.searchsorted(np.asarray(s, np.int64), np.arange(L), side="right")
                     for s in starts]).astype(np.int32)


def _layout(gate_in: bool, gate_out: bool):
    """Where x and the gates lie in the wide array, as the callers lay them
    out: [g_in | g_out | x] (LFM2), x between two strangers (Mamba-2 has
    one before, DeltaNet one after), [g_in | x], [x | g_out]."""
    if gate_in and gate_out:
        return 3 * C, dict(at=2 * C, gate_in=0, gate_out=C)
    if gate_in:
        return 2 * C, dict(at=C, gate_in=0)
    if gate_out:
        return 3 * C, dict(at=C, gate_out=2 * C)
    return 3 * C, dict(at=C)


def _case(taps, bias, silu, gate_in, gate_out, where="mixed", dtype=jnp.float32):
    rng = np.random.default_rng(taps + 2 * bias + 4 * silu + 8 * gate_in + 16 * gate_out)
    width, places = _layout(gate_in, gate_out)
    src = jnp.asarray(rng.standard_normal((2, L, width)), dtype)
    w = jnp.asarray(rng.standard_normal((taps, C)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((C,)), jnp.float32) if bias else None
    dy = jnp.asarray(rng.standard_normal((2, L, C)), jnp.float32)
    seg = jnp.asarray(_segs(where))

    def both(interpret):
        def loss(src, w, b):
            y = sc.conv_chain(src, w, seg, channels=C, bias=b, silu=silu, interpret=interpret,
                              **places)
            return jnp.sum(y * dy), y

        (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2) if bias else (0, 1),
                                           has_aux=True)(src, w, b)
        return (y,) + grads

    return both(False), both(True)


def _close(got, want, tol=1e-6):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want)))


_PIECES = [pytest.param(*c, id="taps{}{}{}{}{}".format(
    c[0], "-bias" * c[1], "-silu" * c[2], "-gate_in" * c[3], "-gate_out" * c[4]))
    for c in itertools.product((3, 4), (False, True), (False, True), (False, True), (False, True))]


@pytest.mark.parametrize("taps,bias,silu,gate_in,gate_out", _PIECES)
def test_kernel_gives_the_xla_forms_output_and_cotangents(taps, bias, silu, gate_in, gate_out):
    assert sc.conv_kind(C, L, jnp.float32, (0,), interpret=True) == "pallas"
    want, got = _case(taps, bias, silu, gate_in, gate_out)
    assert len(got) == 3 + bias  # y, d_src, d_w and the bias's
    for g, w in zip(got, want):
        _close(g, w)
    # what lies beside the parts in the wide array gets a zero cotangent
    width, places = _layout(gate_in, gate_out)
    used = np.zeros(width, bool)
    for start in places.values():
        used[start:start + C] = True
    assert not np.asarray(got[1])[..., ~used].any() and np.asarray(got[1])[..., used].all()


#: the three callers' chains: DeltaNet (4 taps, SiLU), Mamba-2 (4 taps, bias,
#: SiLU), LFM2 (3 taps between two gates)
_CALLERS = {"deltanet": (4, False, True, False, False), "mamba2": (4, True, True, False, False),
            "lfm2": (3, False, False, True, True)}


@pytest.mark.parametrize("where", ["first_slot", "in_halo", "none"])
@pytest.mark.parametrize("caller", sorted(_CALLERS))
def test_a_boundary_where_tiles_meet(caller, where):
    want, got = _case(*_CALLERS[caller], where=where)
    for g, w in zip(got, want):
        _close(g, w)


@pytest.mark.parametrize("caller", sorted(_CALLERS))
def test_bfloat16_input_is_read_as_it_lies(caller):
    """The wide projection in bfloat16, as the chip keeps it: the output is
    the XLA form's; the input's cotangent is rounded to bfloat16 once, so it
    may differ by one step of that rounding."""
    want, got = _case(*_CALLERS[caller], dtype=jnp.bfloat16)
    assert got[1].dtype == jnp.bfloat16
    _close(got[0], want[0])
    _close(got[1], want[1], tol=2.0 ** -8)
    for g, w in zip(got[2:], want[2:]):
        _close(g, w)


def _mixers():
    """name -> (parameters, the mixer as ``(p, x, seg) -> [B, L, D]``) at
    widths whose convolutions are whole lane tiles: 256 channels each."""
    from predictionio_tpu.ops.deltanet import gated_deltanet
    from predictionio_tpu.ops.ssd import mamba2

    rng = np.random.default_rng(11)

    def drawn(**shapes):
        return {name: jnp.asarray(rng.standard_normal(shape) * 0.2, jnp.float32)
                for name, shape in shapes.items()}

    d = 64
    return {
        "deltanet": (drawn(w_qkvz=(d, 384), w_ba=(d, 4), conv_w=(4, 256), A_log=(2,), dt_bias=(2,),
                           o_norm=(64,), w_out=(128, d)),
                     lambda p, x, seg: gated_deltanet(
                         p, x, seg, key_heads=1, value_heads=2, key_dim=64, value_dim=64,
                         eps=1e-6, chunk=16)[0]),
        "mamba2": (drawn(w_in=(d, 384), w_dt=(d, 2), conv_w=(4, 256), conv_b=(256,), A_log=(2,),
                         dt_bias=(2,), D=(2,), norm=(128,), w_out=(128, d)),
                   lambda p, x, seg: mamba2(p, x, seg, heads=2, head_dim=64, state=64, eps=1e-6,
                                            chunk=16)[0]),
        "shortconv": (drawn(w_in=(256, 768), conv_w=(3, 256), w_out=(256, d)),
                      lambda p, x, seg: sc.short_conv(p, jnp.tile(x, (1, 1, 4)), seg)[0]),
    }


@pytest.mark.parametrize("mixer", ["deltanet", "mamba2", "shortconv"])
def test_a_mixer_on_a_tpu_runs_the_kernel_and_gives_what_the_xla_form_gives(monkeypatch, mixer):
    """The three callers as the step calls them, with the backend answered
    as a TPU and the kernel handed to Pallas's interpreter: the mixer's
    output and every gradient against the CPU's XLA chain."""
    p, run = _mixers()[mixer]
    x = jnp.asarray(np.random.default_rng(12).standard_normal((2, L, 64)), jnp.float32)
    seg = jnp.asarray(_segs("mixed"))

    def loss(p, x):
        y = run(p, x, seg)
        return jnp.sum(y * y), y

    def traced(loss=loss):
        return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1), has_aux=True))(p, x)

    want = jax.grad(loss, argnums=(0, 1), has_aux=True)(p, x)
    assert "pallas_call" not in str(traced())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sc, "_params", lambda interpret, real=sc._params: real(True))
    on_tpu = lambda p, x: loss(p, x)  # noqa: E731  (a trace of its own)
    assert str(traced(on_tpu)).count("pallas_call") >= 2  # forward and backward
    got = jax.grad(on_tpu, argnums=(0, 1), has_aux=True)(p, x)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        _close(g, w, tol=2e-5)


def test_a_history_does_not_read_its_neighbour_across_a_tile():
    """x is 1 in the first history and 100 in the second, which starts on a
    tile's first slot: with taps of 1 the output counts the slots of its
    own history it can see."""
    seg = jnp.asarray(np.where(np.arange(L) < 32, 1, 2)[None].astype(np.int32))
    x = jnp.where(seg[..., None] == 1, 1.0, 100.0) * jnp.ones((1, L, C), jnp.float32)
    y = np.asarray(sc.conv_chain(x, jnp.ones((4, C), jnp.float32), seg, channels=C,
                                 interpret=True))
    np.testing.assert_array_equal(y[0, :5, 0], [1, 2, 3, 4, 4])
    np.testing.assert_array_equal(y[0, 30:36, 7], [4, 4, 100, 200, 300, 400])


def test_more_taps_than_a_register_has_rows_run_the_xla_form():
    assert sc.conv_kind(256, 64, "float32", (0,), True, taps=7) == "pallas"
    assert sc.conv_kind(256, 64, "float32", (0,), True, taps=8) == "xla"
    x = jnp.ones((1, 32, 128), jnp.float32)
    y = sc.conv_chain(x, jnp.ones((8, 128), jnp.float32), jnp.ones((1, 32), jnp.int32), channels=128,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(y)[0, :, 0], np.minimum(np.arange(32) + 1, 8))


@pytest.mark.parametrize("channels,length,dtype,offsets,interpret,want", [
    pytest.param(256, 64, "float32", (0,), False, "xla", id="cpu"),
    pytest.param(256, 64, "float32", (0,), True, "pallas", id="interpreted"),
    pytest.param(192, 64, "float32", (0,), True, "xla", id="channels-not-lane-tiles"),
    pytest.param(256, 64, "bfloat16", (0, 256, 512), True, "xla", id="bfloat16-gates"),
    pytest.param(256, 64, "float32", (64,), True, "xla", id="offset-not-a-lane-tile"),
    pytest.param(256, 72, "float32", (0,), True, "xla", id="row-not-whole-halo-blocks"),
    pytest.param(256, 64, "float32", (0, 512), True, "xla", id="parts-not-neighbours"),
    pytest.param(256, 64, "float32", (128, 384), True, "xla", id="parts-not-blocks-of-their-width"),
    pytest.param(4352, 8192, "float32", (4096,), True, "pallas", id="mamba2-widths"),
    pytest.param(2048, 8192, "float32", (0, 2048, 4096), True, "pallas", id="lfm2-widths"),
])
def test_conv_kind(channels, length, dtype, offsets, interpret, want):
    assert sc.conv_kind(channels, length, dtype, offsets, interpret) == want


def test_the_xla_form_runs_where_the_kernel_does_not():
    """A bfloat16 ``gate_dtype`` (the benchmark's control build) and odd
    channels go through ``causal_conv`` even where the kernel is asked for."""
    rng = np.random.default_rng(3)
    bcx = jnp.asarray(rng.standard_normal((1, L, 3 * C)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, C)), jnp.float32)
    seg = jnp.asarray(_segs("mixed")[:1])
    low = sc.gated_conv(bcx, w, seg, jnp.bfloat16, interpret=True)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(low, np.float32),
                                  np.asarray(sc.gated_conv(bcx, w, seg, jnp.bfloat16), np.float32))
    sound = sc.gated_conv(bcx, w, seg, jnp.float32, interpret=True)
    assert sound.dtype == jnp.float32
    _close(sound, sc.gated_conv(bcx, w, seg, jnp.float32))


@pytest.mark.parametrize("length,lanes_cap,want", [
    (8192, 1024, (512, 1024)), (8192, 256, (2048, 256)), (48, 128, (16, 128))])
def test_tiles_divide_the_row_and_the_channels(monkeypatch, length, lanes_cap, want):
    monkeypatch.setattr(sc, "_TILE", 512 * 1024)
    monkeypatch.setattr(sc, "_WIDEST", lanes_cap)
    spec = sc._Chain(1024, 0, None, None, False, False)
    assert sc._tile(spec, length, False) == want
