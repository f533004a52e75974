"""The listed backbones of the sequence template, pinned: what their
tiny configurations compute on a fixed seeded batch must stay what the commit
before the next backbone computed (``granite4h-tiny``: commit 32bdee9, the
parent of the fifth backbone's PR), or, for the fifth, the commit before the
backbone's mixers became one table (``phi4-mini-flash-tiny``: commit d0ce42b,
PR 43), or, for the sixth, what its own PR computed (``keye-vl2-tiny``: PR 45,
with exactly ``topk`` keys a query), or, for the seventh, what ITS own PR
computed (``nemotron3-nano-tiny``: PR 49, layers of one part);
and the five older ones' step programs must lower to the text the sixth's
parent lowered, the sixth's to what the seventh's parent lowered."""

import json

import jax
import numpy as np
import pytest

from predictionio_tpu.models import seq_backbone as bb

VOCAB, L = 50, 64


@pytest.fixture(scope="module")
def batch():
    """Two packed rows of L + 1 slots: three histories and padding in the
    first, one history that fills the second."""
    rng = np.random.default_rng(2)
    rows = rng.integers(0, VOCAB, size=(2, L + 1)).astype(np.int32)
    segs = np.zeros((2, L + 1), np.int32)
    for sid, (lo, hi) in {1: (0, 20), 2: (20, 57), 3: (57, 62)}.items():
        segs[0, lo:hi] = sid
    segs[1, :] = 1
    return rows, segs


PINNED = json.loads("""
{"qwen3next-tiny": {"loss": 3.9226794242858887, "grad_norm": {"embed": 42.86202103688412,
  "final_norm.w": 0.014612602865828718, "head": 0.714324359798024, "periods.ffn": 1.1973556206567664,
  "periods.full": 0.08691547574913118, "periods.linear": 7.205409834054159,
  "periods.norm_in": 0.7365154657629039, "periods.norm_post": 0.22681090023504363}},
 "joyai-flash-tiny": {"loss": 5.116833209991455, "grad_norm": {"dense.ffn": 0.1786237141053716,
  "dense.full": 0.35678529079261606, "dense.norm_in": 0.05811747142576252,
  "dense.norm_post": 0.03350126319130029, "embed": 2.5350993431535684,
  "final_norm.w": 0.01301136403281458, "head": 0.7155275038543362, "mtp.block": 0.03028197180117545,
  "mtp.eh_proj": 0.02868857754427902, "mtp.enorm": 0.004046483760910491,
  "mtp.hnorm": 0.00423206835364789, "mtp.norm": 0.004297533724349888,
  "periods.ffn": 0.11208062946786075, "periods.full": 0.052811122277361754,
  "periods.norm_in": 0.006166508100601532, "periods.norm_post": 0.017627226551916005}},
 "lfm2-tiny": {"loss": 3.956284284591675, "grad_norm": {"dense.conv": 0.5102702058199934,
  "dense.ffn": 0.2573889067213364, "dense.norm_in": 0.08048597162057211,
  "dense.norm_post": 0.04305908614364948, "embed": 4.210059824918104,
  "final_norm.w": 0.01595513999631072, "periods.conv": 0.22144379809589704,
  "periods.ffn": 0.07585715419251786, "periods.full": 0.09164016955651524,
  "periods.norm_in": 0.036699178116064254, "periods.norm_post": 0.01332038945281503}},
 "granite4h-tiny": {"loss": 3.9133141040802, "grad_norm": {"embed": 0.4779150043923512,
  "final_norm.w": 0.0017570282807558048, "periods.ffn": 0.04354429097027376,
  "periods.full": 0.005497379137975552, "periods.norm_in": 0.009530143908606488,
  "periods.norm_post": 0.007683603375504363, "periods.ssm": 0.06981819830080853}},
 "phi4-mini-flash-tiny": {"loss": 3.934260606765747, "grad_norm": {"embed": 3.74777341084151,
  "final_norm.b": 0.012061838697027814, "final_norm.g": 0.01484294619753881,
  "periods.cross": 0.006096933379104272, "periods.ffn": 0.36072661833735686,
  "periods.full": 0.018284754473628158, "periods.gmu": 0.014815461261404308,
  "periods.mamba1": 0.5202352792829213, "periods.norm_in": 0.10984904188512179,
  "periods.norm_post": 0.08390504050254491, "periods.swa": 0.04723355729407075}},
 "keye-vl2-tiny": {"loss": 4.133016109466553, "grad_norm": {"embed": 0.8558340625832418,
  "final_norm.w": 0.01721364968225186, "head": 0.7197617383071163, "periods.dsa": 0.9331903167737758,
  "periods.ffn": 0.05705573140272603, "periods.norm_in": 0.012798044946150227,
  "periods.norm_post": 0.0009832543026727114}},
 "nemotron3-nano-tiny": {"loss": 3.9308419227600098, "grad_norm": {"embed": 2.057600270499885,
  "final_norm.w": 0.01512243031049317, "head": 0.677826406422071, "periods.full": 0.03878747338818655,
  "periods.moe": 0.21130955257304626, "periods.norm_in": 0.05790962085522093,
  "periods.ssm": 0.30691068655779696}}}
""")


@pytest.mark.parametrize("name", sorted(PINNED))
def test_a_listed_configuration_computes_what_it_did_before_this_backbone(name, batch):
    """The loss and the gradient's norm by group of the listed backbones at
    their tiny sizes, on this file's seeded batch and weights drawn from seed
    0, as commit fb37b61 (PR 37, before ``ssm`` layers, the multipliers and
    ``positions: none``) computed the first three on the CPU and commit 32bdee9
    (PR 40, before ``mamba1``, ``gmu``, windows and cross-attention) the
    fourth, commit d0ce42b (PR 43, before the table of mixers) the fifth, and
    PR 45 itself the sixth (sparse attention; the reference holds it: here it is
    held against a later change; the seventh likewise, PR 49). A
    change to a mixer of ``seq_backbone``, ``_layer``, ``logits_of``, ``swiglu`` or the
    loss that moves a listed configuration fails here and not on the
    driver's chip. 1e-4: float32 sums in another order read 1e-6; a
    multiplier applied where it is 1 by default moves every number."""
    cfg = bb.BackboneConfig.load(name)
    params = bb.init_params(cfg, VOCAB, L, 0)
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda mp, r, s: bb.loss_fn(cfg, mp, r, s), has_aux=True))(params, *batch)
    squares = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]:
        key = ".".join(str(k.key) for k in path[:2])
        squares[key] = squares.get(key, 0.0) + float(np.sum(np.asarray(leaf, np.float64) ** 2))
    want = PINNED[name]
    assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
    assert sorted(squares) == sorted(want["grad_norm"])
    for key, value in want["grad_norm"].items():
        assert np.sqrt(squares[key]) == pytest.approx(value, rel=1e-4), key


#: sha256 (first 16 hex digits) of the lowered text of the donated optimizer step
#: (``sequencerec._programs``) of every listed backbone at its tiny size, two
#: rows of 65 slots over 50 items, as commit 690be00 (PR 44, before sparse
#: attention and the chosen mask of ``ops/attention.py``) lowered it here
LOWERED = {
    "qwen3next-tiny": "8647b3ccf9e03740", "joyai-flash-tiny": "ca7aadf33217cfca",
    "lfm2-tiny": "8a0a78b7521aab03", "granite4h-tiny": "af44eb0d1a6e2910",
    "phi4-mini-flash-tiny": "11204e7d06802878",
    # as commit e34b81f (PR 48, the seventh backbone's parent) lowered it
    "keye-vl2-tiny": "9ecf115e83a29fe7"}


@pytest.mark.parametrize("name", sorted(LOWERED))
def test_a_listed_configuration_lowers_to_the_text_it_did_before_this_backbone(name):
    """The step program of a listed backbone is, letter for letter, what the
    commit before the sixth backbone lowered: the chosen mask, its second
    custom VJP and the shared projections' new function change nothing that a
    configuration without sparse-attention layers traces. A later PR that
    changes a listed step on purpose writes the new digests here and says so
    (``python -c`` of this test's four lines on the parent gives the old ones)."""
    import hashlib

    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load(name)
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    params = jax.eval_shape(lambda: bb.init_params(cfg, VOCAB, L, 0))
    rows = jax.ShapeDtypeStruct((2, L + 1), np.int32)
    try:
        text = step.lower(params, jax.eval_shape(opt_init, params), rows, rows).as_text()
    finally:
        step.clear_cache()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == LOWERED[name]


#: sha256 (first 16 hex digits) over the same step's equations, each as "name
#: stack/primitive" with how often it occurs, of those under a ``seq.`` scope,
#: through every sub-jaxpr: as commit 690be00 traced them. The lowered text
#: above carries no name stack, so a cast or a transpose that moves out of
#: ``seq.attn.core`` (seconds that a per-layer metric divides by) shows here alone
SCOPED = {
    "qwen3next-tiny": "d3cddef1dda60909", "joyai-flash-tiny": "84122237e9b90538",
    "lfm2-tiny": "c0849603f5aef9f0", "granite4h-tiny": "2d28a1e6607553c0",
    "phi4-mini-flash-tiny": "d655df2c744808e3",
    "keye-vl2-tiny": "1c9f9836af20be88"}  # the last as commit e34b81f (PR 48) traced it


def _scoped(jaxpr, under=""):
    for eqn in jaxpr.eqns:
        stack = under + "/" + str(eqn.source_info.name_stack)
        yield stack + "/" + eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scoped(sub, stack)


@pytest.mark.parametrize("name", sorted(SCOPED))
def test_a_listed_configuration_keeps_every_operation_under_its_scope(name):
    """What the device-trace metrics read is part of the yardstick: the
    operations of a listed backbone's step stand under the named scopes they
    stood under before the sixth backbone shared its projections."""
    import collections
    import hashlib

    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load(name)
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    params = jax.eval_shape(lambda: bb.init_params(cfg, VOCAB, L, 0))
    rows = jax.ShapeDtypeStruct((2, L + 1), np.int32)
    closed = jax.make_jaxpr(step)(params, jax.eval_shape(opt_init, params), rows, rows)
    count = collections.Counter(s for s in _scoped(closed.jaxpr) if "seq." in s)
    lines = sorted(f"{k} {v}" for k, v in count.items())
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] == SCOPED[name]
