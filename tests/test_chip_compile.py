"""The main path's kernels compiled for the chip that is described, not
attached — at the widths ``chip_smoke.py`` runs (rank 50 padded to 56,
138,493 users x 26,744 items, serving batch 32).

Tier-1 (not slow): about two seconds per case, and the only tests in the
default run that hand a kernel to the TPU compiler. ``interpret=False`` is
passed explicitly — code that asks ``jax.default_backend()`` sees the CPU
in a test. Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped fixture and skipped from
there, never at import: under pytest-xdist every worker imports this file,
and only the worker that runs it may load the TPU library. The compiles
happen in this process, with the persistent compilation cache off (an
executable compiled for a described chip is written but cannot be read
back without one, and would warn on the next run).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from predictionio_tpu.ops.pallas_kernels import (
    gramian_fused,
    spd_solve_t,
    top_k_streaming,
)

N_USERS, N_ITEMS, RANK, RANK_PAD = 138_493, 26_744, 50, 56


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *avals, **static):
    lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(
        *avals, **static
    )
    compiled = lowered.compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    return compiled


def test_spd_solve_rank50(one_chip):
    compiled = _compile(
        functools.partial(spd_solve_t, interpret=False),
        _sds(one_chip, (RANK_PAD, RANK_PAD, 1024), jnp.float32),
        _sds(one_chip, (RANK_PAD, 1024), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n", [8, 16, 32])
def test_spd_solve_dual_widths(one_chip, n):
    """The solver at the sizes the dual form gives it: a bucket of
    ``k < rank`` slots solves ``max(8, k) x max(8, k)`` systems, one
    16,384-row block at a time."""
    compiled = _compile(
        functools.partial(spd_solve_t, interpret=False),
        _sds(one_chip, (n, n, 16384), jnp.float32),
        _sds(one_chip, (n, 16384), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b, k, table_dtype", [
    # the smoke's own bucket blocks [rows, width]; each of the first three
    # was refused for the chip before PR 22 (rank-1 SMEM ridge block;
    # scoped VMEM at 128-row output blocks; a 4-row index block)
    (1024, 512, jnp.float32),
    (4096, 128, jnp.float32),
    (32, 8192, jnp.float32),
    (8, 32768, jnp.float32),
    (4, 8192, jnp.bfloat16),
])
def test_gramian_fused_item_table(one_chip, b, k, table_dtype):
    compiled = _compile(
        functools.partial(gramian_fused, interpret=False),
        _sds(one_chip, (N_ITEMS, RANK_PAD), table_dtype),
        _sds(one_chip, (b, k), jnp.int32),
        _sds(one_chip, (b, k), jnp.float32),
        _sds(one_chip, (b, k), jnp.float32),
        _sds(one_chip, (b,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_als_half_step_with_its_kernels(one_chip, monkeypatch):
    """One whole ALS half-step as ``pio train`` runs it on the chip:
    pallas solver + fused Gramian over every bucket of a power-law
    problem. The program picks interpret mode from
    ``jax.default_backend()``, which is the CPU in a test — steered here,
    or the compile would pass on interpreted kernels and say nothing
    (how the fused kernel's faults stayed hidden until the chip)."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.tools.prewarm_cache import _stage_avals

    rng = np.random.default_rng(0)
    n_u, n_i, nnz = 6_000, 1_500, 150_000
    w = 1.0 / np.arange(1, n_u + 1) ** 0.8
    users = rng.choice(n_u, size=nnz, p=w / w.sum())
    items = rng.integers(0, n_i, nnz)
    vals = rng.integers(1, 6, nnz).astype(np.float32)
    by_item = als.bucketize(items, users, vals, n_i, n_u, pad_to_blocks=True)
    assert max(b.width for b in by_item.buckets) >= 512  # wide buckets too
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(
        als._als_half,
        _sds(one_chip, (n_u, RANK), jnp.float32),
        _stage_avals(by_item, one_chip),
        _sds(one_chip, (), jnp.float32),
        _sds(one_chip, (), jnp.float32),
        n_rows=n_i, rank=RANK, implicit=False, solve_mode="pallas",
        mesh=None, gather_dtype="f32", side="item",
    )
    # a solver call and a fused-build call per bucket wide enough for it
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 4
    # what the benchmark's trace reduction finds its events by
    # (docs/observability.md): the kernels' names on their instructions,
    # and the name stack side / rung / own width / phase in ``op_name``
    assert "%gramian_fused" in text and "%spd_solve_t" in text
    widest = max(b.width for b in by_item.buckets)
    bucket = f"als.item_side/als.w{widest}/als.k{widest}"
    chunk = f"{bucket}/while/body/closed_call"
    assert f"{chunk}/als.gramian/" in text
    assert f"{chunk}/als.solve/spd_solve_t/pallas_call" in text
    assert f"{bucket}/als.scatter/scatter" in text


@pytest.mark.parametrize("block, width, rung", [
    # the blocks ``train-amazonbooks`` runs under the rank, [rows, width]:
    # the ladder's narrow rungs beside the width 8 it always had
    (16384, 1, 8), (16384, 2, 8), (16384, 4, 8), (16384, 8, 8),
    (16384, 16, 32), (8192, 32, 32),
])
def test_narrow_bucket_half_step(one_chip, monkeypatch, block, width, rung):
    """One bucket narrower than the rank, as the user side of the
    benchmark's cell solves it (explicit, so in the dual form): XLA's
    gather, the ``[k, k, B]`` system from a float32 product at
    ``HIGHEST``, the Pallas solver at ``n = max(8, k)``, the row from
    ``[k] x [k, 56]``. The block is what ``_BLOCK_ROWS`` gives the
    width; no ``[56, 56, B]`` system (205 MB at 16,384 rows) is left
    in the program."""
    from predictionio_tpu.ops import als

    assert als._block_rows_for(width) == block
    chunks, n_users, n_items = 3, 8_026_324, 2_330_066  # the cell's tables
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(
        als._als_half,
        _sds(one_chip, (n_items, RANK), jnp.float32),
        ((
            _sds(one_chip, (chunks, block), jnp.int32),
            _sds(one_chip, (chunks, block, width), jnp.int32),
            _sds(one_chip, (chunks, block, width), jnp.float32),
            _sds(one_chip, (chunks, block), jnp.int32),
        ),),
        _sds(one_chip, (), jnp.float32),
        _sds(one_chip, (), jnp.float32),
        n_rows=n_users, rank=RANK, implicit=False, solve_mode="pallas",
        mesh=None, gather_dtype="f32", side="user",
    )
    text = compiled.as_text()
    assert "%spd_solve_t" in text and "%gramian_fused" not in text
    # side / rung / own width / phase: ``als.w8`` holds every width up to 8
    chunk = f"als.user_side/als.w{rung}/als.k{width}/while/body/closed_call"
    assert f"{chunk}/als.gather/" in text
    assert f"{chunk}/als.gramian/bkr,bjr->kjb/" in text
    assert f"{chunk}/als.solve/spd_solve_t/pallas_call" in text
    assert f"{chunk}/als.solve/bkr,kb->br/" in text
    assert f"als.user_side/als.w{rung}/als.k{width}/als.scatter/scatter" in text
    # float32 at HIGHEST wherever the compiler kept a product as one (it
    # writes the expansion, and width 1's system, as multiply and
    # reduce): the text names a precision only where it is not the default
    products = text.count(" convolution(")
    assert products == text.count("operand_precision={highest,highest}")
    assert products >= (width > 1)
    n = max(8, width)
    assert f"f32[{n},{n},{block}]" in text
    assert f"f32[{RANK_PAD},{RANK_PAD},{block}]" not in text
    normal_equations = RANK_PAD * RANK_PAD * block * 4
    padded_table = n_items * RANK_PAD * 4
    assert (
        compiled.memory_analysis().temp_size_in_bytes
        < padded_table + normal_equations
    )


def _bucket_avals(sharding, chunks, block, width):
    """One staged bucket (rows, idx, val, counts) as ``stage`` ships it."""
    return (
        _sds(sharding, (chunks, block), jnp.int32),
        _sds(sharding, (chunks, block, width), jnp.int32),
        _sds(sharding, (chunks, block, width), jnp.float32),
        _sds(sharding, (chunks, block), jnp.int32),
    )


@pytest.mark.parametrize("width", [8, 128, 2048])
def test_implicit_half_step_rank50(one_chip, monkeypatch, width):
    """The implicit half-step no benchmark cell runs, at the smoke's
    tables: its base matrix is ``YtY + lambda n I``, so no width goes
    dual. Under the rank the ``[56, 56, B]`` systems come from the
    weighted einsum; from the rank up ``gramian_fused`` takes ``YtY``
    in; both feed the Pallas solver at n = 56."""
    from predictionio_tpu.ops import als

    block = als._block_rows_for(width)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(
        als._als_half,
        _sds(one_chip, (N_ITEMS, RANK), jnp.float32),
        (_bucket_avals(one_chip, 2, block, width),),
        _sds(one_chip, (), jnp.float32),
        _sds(one_chip, (), jnp.float32),
        n_rows=N_USERS, rank=RANK, implicit=True, solve_mode="pallas",
        mesh=None, gather_dtype="f32", side="user",
    )
    text = compiled.as_text()
    bucket = f"als.user_side/als.w{als._scope_rung(width)}/als.k{width}"
    assert "als.user_side/als.yty/" in text
    assert f"{bucket}/while/body/closed_call/als.solve/spd_solve_t/pallas_call" in text
    assert ("%gramian_fused" in text) == (width >= RANK)
    if width < RANK:
        assert f"f32[{RANK_PAD},{RANK_PAD},{block}]" in text
        assert f"{bucket}/while/body/closed_call/als.gramian/bkr,bk,bks->rsb/" in text


@pytest.mark.parametrize("factor_sharding", ["replicated", "model"])
def test_mesh_half_step_2x2(topo, monkeypatch, factor_sharding):
    """``als_train(mesh=...)``'s half-step on the 2 x 2 topology: solve
    rows over ``data``, the table replicated or row-sharded over
    ``model``. The Pallas kernels do not partition themselves, so the
    solver (a narrow bucket's ``k x k`` systems) and the fused build (a
    wide bucket) each run inside ``shard_map`` on a device's own rows."""
    from predictionio_tpu.ops import als
    from predictionio_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, MeshConfig, create_mesh,
    )

    mesh = create_mesh(MeshConfig(((DATA_AXIS, 2), (MODEL_AXIS, 2))), topo.devices)
    table = NamedSharding(
        mesh, P(MODEL_AXIS) if factor_sharding == "model" else P())
    rows = NamedSharding(mesh, P(None, DATA_AXIS))
    scalar = NamedSharding(mesh, P())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = _compile(
        als._als_half_sharded(table),
        _sds(table, (N_ITEMS, RANK), jnp.float32),
        (_bucket_avals(rows, 2, 4096, 8), _bucket_avals(rows, 2, 64, 512)),
        _sds(scalar, (), jnp.float32),
        _sds(scalar, (), jnp.float32),
        # a table sharded over ``model`` holds an even number of rows
        n_rows=N_USERS + 1, rank=RANK, implicit=False, solve_mode="pallas",
        mesh=mesh, gather_dtype="f32", side="user",
    )
    text = compiled.as_text()
    narrow = "als.user_side/als.w8/als.k8/while/body/closed_call"
    wide = "als.user_side/als.w512/als.k512/while/body/closed_call"
    assert f"{narrow}/als.solve/shard_map/spd_solve_t/pallas_call" in text
    assert f"{wide}/shard_map/als.gramian/" in text and "%gramian_fused" in text
    assert f"{wide}/shard_map/als.solve/spd_solve_t/pallas_call" in text
    assert len(compiled.input_shardings[0][0].device_set) == 4


@pytest.mark.parametrize("n_excl", [0, 64])
def test_top_k_streaming_catalog(one_chip, n_excl):
    def fn(q, items, *excl):
        return top_k_streaming(
            q, items, 16, exclude_idx=excl[0] if excl else None,
            interpret=False,
        )

    avals = [
        _sds(one_chip, (64, RANK), jnp.float32),
        _sds(one_chip, (N_ITEMS, RANK), jnp.float32),
    ]
    if n_excl:
        avals.append(_sds(one_chip, (64, n_excl), jnp.int32))
    compiled = _compile(fn, *avals)
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_serving_program(one_chip):
    """What ``/queries.json`` dispatches for this catalog: the fused
    score + top-k entry on its dense path (26,744 items at B=32 is under
    the streaming bar), user-row gather included."""
    from predictionio_tpu.ops.scoring import top_k_for_users_fused

    compiled = _compile(
        top_k_for_users_fused,
        _sds(one_chip, (N_USERS, RANK), jnp.float32),
        _sds(one_chip, (N_ITEMS, RANK), jnp.float32),
        _sds(one_chip, (32,), jnp.int32),
        k=16, mode="never",
    )
    assert "tpu_custom_call" not in compiled.as_text()


def test_sharded_half_step_2x2(topo):
    """One ``pio train --shards 4`` half-step (solve the user side from
    the item table) as one program across the 2x2 mesh: slabs and tables
    sharded over the shard axis, the all-gather inside."""
    from predictionio_tpu.ops import als_sharded
    from predictionio_tpu.parallel.mesh import MeshConfig, create_mesh

    shards, n_u, n_i, nnz = 4, 1_000, 300, 8_000
    rng = np.random.default_rng(0)
    w = 1.0 / np.arange(1, n_u + 1) ** 0.9  # most users hold 1 to 4
    users = rng.choice(n_u, size=nnz, p=w / w.sum()).astype(np.int32)
    items = rng.integers(0, n_i, nnz).astype(np.int32)
    vals = rng.integers(1, 6, nnz).astype(np.float32)
    user_plan = als_sharded.plan_side(
        np.bincount(users, minlength=n_u), shards, rank=RANK)
    item_plan = als_sharded.plan_side(
        np.bincount(items, minlength=n_i), shards, rank=RANK)
    slabs, _ = als_sharded._build_side(
        users, items, vals, user_plan, item_plan,
        als_sharded.DEFAULT_BUCKET_WIDTHS,
    )
    assert {1, 2, 4, 8, 16, 32, 128} <= {slab[1].shape[-1] for slab in slabs}
    mesh = create_mesh(
        MeshConfig(((als_sharded.SHARD_AXIS, shards),)), topo.devices[:shards]
    )
    sharded = NamedSharding(mesh, P(als_sharded.SHARD_AXIS))
    replicated = NamedSharding(mesh, P())
    compiled = _compile(
        als_sharded._half_sharded,
        _sds(sharded, (shards * item_plan.cap, RANK), jnp.float32),
        tuple(
            tuple(_sds(sharded, a.shape, a.dtype) for a in slab)
            for slab in slabs
        ),
        _sds(replicated, (), jnp.float32),
        _sds(replicated, (), jnp.float32),
        mesh=mesh, rank=RANK, implicit=False, gather_dtype="f32",
        cap_x=user_plan.cap,
    )
    assert "all-gather" in compiled.as_text()
    assert len(compiled.input_shardings[0][0].device_set) == shards


@pytest.fixture
def as_tpu(monkeypatch):
    """Code that asks for the backend is told "tpu" (here it is the CPU),
    and the delta rule, the sparse-attention core and the indexers' loss, whose
    jits keep a trace made under one answer for the next caller with the same
    shapes, are traced anew on both sides."""
    from predictionio_tpu.ops.attention import chosen_attention
    from predictionio_tpu.ops.deltanet import gated_delta_rule
    from predictionio_tpu.ops.dsa import index_loss

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # jits that ask for the backend while traced
    kept = (gated_delta_rule, chosen_attention, index_loss)
    for fn in kept:
        fn.clear_cache()
    yield
    for fn in kept:
        fn.clear_cache()


# -- the sequence backbone's programs at the shapes of train-qwen3next-packed8k:
# rows of 8,192 slots, Qwen3-Next-80B-A3B widths, bfloat16 products
SEQ_L = 8192

#: temporaries of the three step programs with a short convolution as commit
#: 90f0b5c (PR 39) compiled them, its XLA chain of pads and selects in them
_TEMPORARIES_BEFORE_THE_KERNEL = {
    "qwen3next": 6_815_364_096, "lfm2": 5_393_792_512 + 2 * 8192 * 2048 * 4,
    "granite4h": 4_765_453_312}


def _conv_under(compiled, scope: str, name: str = ""):
    """The short convolution of a compiled step, read from its text: the
    Pallas kernel's calls under ``scope`` (forward, the layer's
    recomputation, backward: three a layer) and no ``pad`` of a float32
    [rows, slots, C] array along its slots there, the shifted copies the XLA
    chain made of every tap (DeltaNet's cotangents of q, k, v are still put
    side by side by pads along the channels); temporaries no larger than
    before the kernel, but for the float32 ``y`` LFM2's chain now hands out
    (XLA's chain rounded it to bfloat16 in its last pass: PERF.md section 6);
    without ``name``, a layer alone: no temporaries are compared."""
    under = [line for line in compiled.as_text().splitlines() if scope in line]
    calls = [line for line in under if 'custom_call_target="tpu_custom_call"' in line]
    pads = [line for line in under
            if re.search(r"= f32\[\d+,\d+,\d+\]\S* pad\(.*padding=\d+_\d+x(?!0_0x)", line)]
    assert not pads, pads[0][:300]
    if name:
        assert (compiled.memory_analysis().temp_size_in_bytes
                <= _TEMPORARIES_BEFORE_THE_KERNEL[name])
    return len(calls)


@pytest.mark.parametrize("walk,state", [
    ("scan", "float32"), ("pallas", "float32"), ("pallas", "bfloat16")])
def test_delta_rule_scan_row_of_8k(one_chip, request, walk, state):
    """The chunked delta rule and its gradient for one packed row: 32
    value heads of 128 x 128 state, 128 chunks of 64. The walk over the
    chunks as the ``lax.scan`` (what the code picks where the backend is
    not a TPU, as here) and as the Pallas kernel (the test answers for the
    backend), which the benchmark's control build runs with state and
    gates in bfloat16."""
    from predictionio_tpu.ops.deltanet import gated_delta_rule

    if walk == "pallas":
        request.getfixturevalue("as_tpu")

    def loss(q, k, v, g, beta, seg):
        return gated_delta_rule(
            q, k, v, g, beta, seg, chunk=64, compute_dtype=jnp.bfloat16,
            state_dtype=jnp.dtype(state), gate_dtype=jnp.dtype(state)).sum()

    qkv = _sds(one_chip, (1, SEQ_L, 32, 128), jnp.bfloat16)
    gate = _sds(one_chip, (1, SEQ_L, 32), jnp.float32)
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), qkv, qkv, qkv, gate, gate,
        _sds(one_chip, (1, SEQ_L), jnp.int32))
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30
    text = compiled.as_text()
    # one walk forward and one in reverse, and no third
    kernels = text.count('custom_call_target="tpu_custom_call"')
    assert kernels == (2 if walk == "pallas" else 0)
    # the preparation builds each array once: a row of a 16 x 16 block
    # written in place copied the whole lane-padded array (PR 35)
    in_place = [line for line in text.splitlines()
                if " dynamic-update-slice(" in line and "seq.deltanet.scan.prep" in line]
    assert not in_place, in_place[:3]
    # and the forward preparation writes the diagonal blocks once: the
    # compiler computes a block's rows apart (a select over the whole block
    # for every row would be fifteen passes over the padded array again)
    entry = text[text.index("ENTRY "):]
    blocks = [line for line in entry.splitlines()
              if re.match(r"\s*(ROOT )?%\S+ = f32\[128,1,32,4,16,16\]", line)
              and "seq.deltanet.scan.prep" in line and "transpose(jvp" not in line]
    assert len(blocks) <= 2, len(blocks)


@pytest.mark.parametrize("heads,kv_heads,head_dim", [(16, 2, 256), (4, 4, 64)])
def test_packed_attention_two_rows_of_8k(one_chip, heads, kv_heads, head_dim):
    """Attention with a segment mask over two packed rows of 8,192 slots,
    forward and backward, at the cell's widths (16 query heads on 2
    key/value heads of 256) and the shipped preset's (4 heads of 64)."""
    from predictionio_tpu.ops.attention import flash_attention

    def loss(q, k, v, seg):
        return flash_attention(q, k, v, segment_ids=seg).astype(jnp.float32).sum()

    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)),
        _sds(one_chip, (2, heads, SEQ_L, head_dim), jnp.bfloat16),
        _sds(one_chip, (2, kv_heads, SEQ_L, head_dim), jnp.bfloat16),
        _sds(one_chip, (2, kv_heads, SEQ_L, head_dim), jnp.bfloat16),
        _sds(one_chip, (2, SEQ_L), jnp.int32))
    # no [pairs, B, H, bq, bk] stack of score tiles (4 GiB when the
    # compiler can count the loop's trips)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30
    assert "tpu_custom_call" not in compiled.as_text()


#: the expert layer at the three sequence cells' widths, and the temporaries
#: of its gradient's program at the parent of PR 37 (7be4e7e: every pass
#: inside one ``scan``), compiled here for v5e
_EXPERT_LAYER_CASES = {
    "32_of_512": (dict(d=2048, f=512, e=512, held=32, top_k=10), 2_978_895_360),
    "16_of_256": (dict(d=2048, f=768, e=256, held=16, top_k=8, scoring="sigmoid", scale=2.5), 2_410_094_080),
    "8_of_64": (dict(d=2048, f=1536, e=64, held=8, top_k=4, scoring="sigmoid"), 2_708_591_104),
}
_expert_layer_programs = {}


def _expert_layer_gradient(one_chip, case):
    """The layer's gradient (parameters and input; the counters as aux) at
    16,384 tokens, compiled once a case for the tests that read it."""
    from predictionio_tpu.ops.moe import expert_layer

    if case not in _expert_layer_programs:
        sizes = dict(_EXPERT_LAYER_CASES[case][0])
        d, f, e, held = (sizes.pop(name) for name in ("d", "f", "e", "held"))
        w = lambda *shape: _sds(one_chip, shape, jnp.float32)  # noqa: E731
        params = {"router": w(d, e),
                  "experts": {"wg": w(held, d, f), "wu": w(held, d, f), "wd": w(held, f, d)}}
        if case != "8_of_64":  # LFM2's layer has no shared expert
            params["shared"] = {"wg": w(d, f), "wu": w(d, f), "wd": w(f, d)}
        if "scoring" in sizes:  # the sigmoid routers carry a bias, Qwen3-Next's shared expert a gate
            params["router_bias"] = w(e)
        else:
            params["shared_gate"] = w(d)

        def loss(p, x):
            y, counters = expert_layer(p, x, first=0, compute_dtype=jnp.bfloat16, **sizes)
            return y.sum(), counters

        _expert_layer_programs[case] = _compile(
            jax.grad(loss, argnums=(0, 1), has_aux=True), params, w(2 * SEQ_L, d))
    return _expert_layer_programs[case]


def test_expert_layer_16k_tokens_32_of_512(one_chip):
    """Router over 512, the held experts' grouped products (XLA lowers
    ``ragged_dot`` to a Mosaic kernel on the chip), shared expert; forward
    and backward at 16,384 tokens."""
    assert "ragged" in _expert_layer_gradient(one_chip, "32_of_512").as_text()


# -- and at the shapes of train-joyai-long8k: JoyAI-LLM-Flash widths, latent
# attention on keys of 192 and values of 128, 16 of 256 experts
def _report(name, compiled):
    stats = compiled.memory_analysis()
    print(f"{name}: {stats}")
    return stats


@pytest.mark.parametrize("kernel", ["xla", "splash"])
def test_latent_attention_two_rows_of_8k(one_chip, kernel):
    """The attention core with a value width of its own, forward and
    backward: the XLA loop (the cell's control runs it) and JAX's Pallas
    kernel (the cell's timed path), 32 heads."""
    from predictionio_tpu.ops.attention import flash_attention, splash_attention

    def loss(q, k, v, seg):
        if kernel == "splash":
            return splash_attention(q, k, v, seg).astype(jnp.float32).sum()
        return flash_attention(q, k, v, segment_ids=seg).astype(jnp.float32).sum()

    wide = _sds(one_chip, (2, 32, SEQ_L, 192), jnp.bfloat16)
    compiled = _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), wide, wide,
        _sds(one_chip, (2, 32, SEQ_L, 128), jnp.bfloat16), _sds(one_chip, (2, SEQ_L), jnp.int32))
    stats = _report(f"latent attention core, {kernel}", compiled)
    assert stats.temp_size_in_bytes < 4 * 2**30
    assert ("tpu_custom_call" in compiled.as_text()) == (kernel == "splash")


def test_expert_layer_16k_tokens_16_of_256_sigmoid(one_chip):
    """Sigmoid router over 256 with its bias, 8 a token, 16 held experts of
    768, the shared expert without a gate; forward and backward."""
    compiled = _expert_layer_gradient(one_chip, "16_of_256")
    _report("expert layer, 16 of 256", compiled)
    assert "ragged" in compiled.as_text()


def _whiles_outside_conditionals(hlo: str):
    """The ``while`` instructions of a compiled module that no
    ``conditional``'s branch encloses: found from the entry computation
    through every called computation but the branches."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            bodies[name] = []
            if head.group(1):
                entry = name
        elif name is not None:
            bodies[name].append(line)
    seen, todo, found = set(), [entry], []
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in bodies[name]:
            if re.search(r"\) while\(", line):
                found.append(line.strip())
            if " conditional(" not in line:  # a branch is run only where its predicate says so
                todo += re.findall(r"(?:body|condition|calls|to_apply)=%?([\w.\-]+)", line)
    return found


@pytest.mark.parametrize("case", sorted(_EXPERT_LAYER_CASES))
def test_the_usual_step_of_the_expert_layer_accumulates_no_weight_cotangent_a_pass(one_chip, case):
    """The gradient of the layer at 16,384 tokens and the three cells'
    widths: all of a step's assignments could need 8, 8 and 4 passes, the
    usual step needs one. That one lies in line; the loop over the others,
    which carries the held experts' cotangents, lies inside a
    ``conditional``'s branch, so the usual step carries, fills and adds no
    array of the experts' shape a pass. And the program needs no more
    temporaries than it did with every pass in one loop."""
    sizes, parent_temp = _EXPERT_LAYER_CASES[case]
    d, f, held = sizes["d"], sizes["f"], sizes["held"]
    compiled = _expert_layer_gradient(one_chip, case)
    hlo = compiled.as_text()
    carried = re.compile(rf"f32\[{held},({d},{f}|{f},{d})\]")
    assert [line for line in re.findall(r".*\) while\(.*", hlo) if carried.search(line)]  # the overflow loop
    assert not [line for line in _whiles_outside_conditionals(hlo) if carried.search(line)]
    stats = _report(f"expert layer's gradient, {case}", compiled)
    print(f"  temporaries {stats.temp_size_in_bytes:,} B; the parent's {parent_temp:,} B")
    assert stats.temp_size_in_bytes <= parent_temp


def test_latent_sparse_layer_two_rows_of_8k(one_chip, monkeypatch):
    """One sparse layer of the shipped configuration as the step runs it
    (recomputed in the backward pass, the Pallas core: the code asks for
    the backend, which here is the CPU, so the test answers for it)."""
    from predictionio_tpu.models import seq_backbone as bb

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = bb.BackboneConfig.load("joyai-flash-48b-a3b-ep16")
    assert cfg.attn_kernel == "splash"
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, 16160, SEQ_L, 0))
    block = jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), shapes["mtp"]["block"])
    layer = bb._layer_fn(cfg, "full", None, "auto")

    def loss(blk, x, seg):
        y, counters, _ = layer(x, seg, bb.positions_of(seg), blk["norm_in"], blk["full"],
                               blk["norm_post"], blk["ffn"])
        return y.sum(), counters["dropped"]

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1), has_aux=True), block,
        _sds(one_chip, (2, SEQ_L, 2048), jnp.float32), _sds(one_chip, (2, SEQ_L), jnp.int32))
    stats = _report("latent-attention sparse layer", compiled)
    assert "tpu_custom_call" in compiled.as_text() and "ragged" in compiled.as_text()
    assert stats.temp_size_in_bytes < 6 * 2**30


def test_qwen3next_step_two_rows_of_8k_fits_the_chip(one_chip, as_tpu):
    """The whole optimizer step of ``train-qwen3next-packed8k`` (2 rows of
    8,193 slots, 626 M parameters with their AdamW moments, donated) as
    the job compiles it. The chip's compiler refuses a program that does
    not fit the chip's 15.75 GiB, and this one stands close to that: what
    a change keeps alive beside it shows here first."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load("qwen3next-80b-a3b-ep16")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 18992, SEQ_L, 0)))
    rows = _sds(one_chip, (2, SEQ_L + 1), jnp.int32)
    try:
        compiled = _compile(step, params, on_chip(jax.eval_shape(opt_init, params)), rows, rows)
    finally:
        step.clear_cache()  # the job's own program object, kept by ``_programs``
    _report("qwen3next step", compiled)
    assert bb.mechanisms(cfg, SEQ_L) == {"delta_rule_walk": "pallas", "conv": "pallas"}
    # the period's three DeltaNet layers stacked in one scan, a row at a time
    assert _conv_under(compiled, "seq.deltanet.conv", "qwen3next") >= 3


# -- and at the shapes of train-lfm2-packed8k: LFM2-24B-A2B widths, gated short
# convolutions beside grouped-query attention on heads of 64, 8 of 64 experts
def test_short_conv_layer_two_rows_of_8k(one_chip, as_tpu):
    """One sparse layer whose mixer is the gated short convolution, as the
    step runs it (recomputed in the backward pass): three taps over 2,048
    channels in the Pallas kernel, router over 64, 8 held experts of 1,536
    and no shared one."""
    from predictionio_tpu.models import seq_backbone as bb

    cfg = bb.BackboneConfig.load("lfm2-24b-a2b-ep8")
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, 8192, SEQ_L, 0))["periods"]
    on_chip = lambda tree, *at: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape[len(at):], s.dtype), tree)
    block = {"conv": on_chip(shapes["conv"], 0, 0),
             **{name: on_chip(shapes[name], 0, 0) for name in ("norm_in", "norm_post", "ffn")}}
    layer = bb._layer_fn(cfg, "conv", None, "auto")

    def loss(blk, x, seg):
        y, counters, ran = layer(x, seg, bb.positions_of(seg), blk["norm_in"], blk["conv"],
                                 blk["norm_post"], blk["ffn"])
        return y.sum(), (counters["dropped"], ran["y"])

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1), has_aux=True), block,
        _sds(one_chip, (2, SEQ_L, 2048), jnp.float32), _sds(one_chip, (2, SEQ_L), jnp.int32))
    stats = _report("short-convolution sparse layer", compiled)
    assert "ragged" in compiled.as_text()  # the grouped products: the compiler's own kernel
    assert stats.temp_size_in_bytes < 6 * 2**30
    # forward, the layer's recomputation, backward; the chain's own checkpoint
    # recomputes nothing, because the kernel's backward pass asks for no output
    assert _conv_under(compiled, "seq.shortconv.conv") == 3


def test_lfm2_step_two_rows_of_8k_fits_the_chip(one_chip, as_tpu):
    """The whole optimizer step of ``train-lfm2-packed8k`` (2 rows of 8,193
    slots, 469 M parameters with their AdamW moments, donated) as the job
    compiles it: arguments 5.63 GB, temporaries 6.94 GB when this was
    written (``PERF.md`` section 4)."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load("lfm2-24b-a2b-ep8")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 8192, SEQ_L, 0)))
    rows = _sds(one_chip, (2, SEQ_L + 1), jnp.int32)
    try:
        compiled = _compile(step, params, on_chip(jax.eval_shape(opt_init, params)), rows, rows)
    finally:
        step.clear_cache()  # the job's own program object, kept by ``_programs``
    stats = _report("lfm2 step", compiled)
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes < 15 * 2**30
    assert cfg.mixers() == {"gqa": 1, "shortconv": 4}
    assert bb.mechanisms(cfg, SEQ_L) == {"conv": "pallas"}
    assert _conv_under(compiled, "seq.shortconv.conv", "lfm2") >= 3


# -- and at the shapes of train-granite4h-packed: granite-4.0-h-micro widths, nine
# Mamba-2 layers (64 heads of 64 on a state of 128, chunks of 256) beside one
# grouped-query attention layer, ONE row of 8,192 slots a step
#: the scan's kernel a Mamba-2 layer and step: forward once (the layer's
#: recomputation keeps its outputs) and backward once
_SCAN_CALLS_A_LAYER = 2


@pytest.mark.parametrize("kind", ["pallas", "xla"])
def test_ssd_scan_one_row_of_8k(one_chip, as_tpu, kind):
    """The state-space scan alone, forward and backward, at the cell's shape:
    the kernel pair (a chunk's [256, 256] matrices and the states in VMEM;
    what the backward pass keeps is the state entering every chunk, 67 MB)
    and XLA's batch products (the control build's bfloat16 state takes
    them: a float32 [256, 256] matrix a head and chunk is 537 MB)."""
    from predictionio_tpu.ops import ssd

    low = {} if kind == "pallas" else dict(state_dtype=jnp.bfloat16, gate_dtype=jnp.bfloat16)
    assert ssd.scan_kind(64, 64, 128, SEQ_L, 256, *low.values()) == kind

    def loss(u, dt, a, b, c, seg):
        with jax.named_scope("seq.ssm.scan"):
            return ssd.ssd_scan(u, dt, a, b, c, seg, chunk=256, compute_dtype=jnp.bfloat16,
                                **low).sum()

    state = _sds(one_chip, (1, SEQ_L, 128), jnp.bfloat16)
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), _sds(one_chip, (1, SEQ_L, 64, 64), jnp.bfloat16),
        _sds(one_chip, (1, SEQ_L, 64), jnp.float32), _sds(one_chip, (64,), jnp.float32),
        state, state, _sds(one_chip, (1, SEQ_L), jnp.int32))
    stats = _report(f"state-space scan ({kind})", compiled)
    text = compiled.as_text().splitlines()
    calls = [line for line in text
             if 'custom_call_target="tpu_custom_call"' in line and "seq.ssm.scan" in line]
    matrices = [line for line in text if re.search(r"= f32\[[\d,]*256,256\]", line)]
    # the kernel: forward and backward, and no [256, 256] matrix a head and
    # chunk in HBM; its temporaries are y's cotangent, the kept states and
    # what XLA lays out a slot and head
    assert len(calls) == (2 if kind == "pallas" else 0)
    assert bool(matrices) == (kind == "xla")
    assert stats.temp_size_in_bytes < (0.4 if kind == "pallas" else 2) * 2**30


def test_mamba2_layer_one_row_of_8k(one_chip, as_tpu):
    """One Mamba-2 layer with its dense SwiGLU as the step runs it
    (recomputed in the backward pass; the scan's kernel pair keeps ``y`` and
    the states entering the chunks over that recomputation): the [256, 256]
    matrices a head and chunk, the largest arrays of XLA's form, exist in
    VMEM alone."""
    from predictionio_tpu.models import seq_backbone as bb

    cfg = bb.BackboneConfig.load("granite4h-micro-vp8")
    shapes = jax.eval_shape(lambda: bb.init_params(cfg, 12544, SEQ_L, 0))["periods"]
    on_chip = lambda tree, *at: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape[len(at):], s.dtype), tree)
    block = {"ssm": on_chip(shapes["ssm"], 0, 0),
             **{name: on_chip(shapes[name], 0, 0) for name in ("norm_in", "norm_post", "ffn")}}
    layer = bb._layer_fn(cfg, "ssm", None, "auto")

    def loss(blk, x, seg):
        y, _, ran = layer(x, seg, bb.positions_of(seg), blk["norm_in"], blk["ssm"],
                          blk["norm_post"], blk["ffn"])
        return y.sum(), ran["y"]

    compiled = _compile(
        jax.grad(loss, argnums=(0, 1), has_aux=True), block,
        _sds(one_chip, (1, SEQ_L, 2048), jnp.float32), _sds(one_chip, (1, SEQ_L), jnp.int32))
    stats = _report("mamba-2 layer", compiled)
    assert stats.temp_size_in_bytes < 1.5 * 2**30  # 1.96 GB with XLA's form
    text = compiled.as_text()
    assert "while" not in text.split("ENTRY")[1]  # the walk over chunks is the kernel's grid
    assert not re.search(r"= f32\[[\d,]*256,256\]", text)
    scan = [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and "seq.ssm.scan" in line]
    assert len(scan) == _SCAN_CALLS_A_LAYER
    assert _conv_under(compiled, "seq.ssm.conv") == 3


def test_granite4h_step_one_row_of_8k_fits_the_chip(one_chip, as_tpu):
    """The whole optimizer step of ``train-granite4h-packed`` (1 row of
    8,193 slots, 772 M parameters with their AdamW moments, donated) as the
    job compiles it: arguments 9.27 GB, temporaries 4.77 GB with XLA's form
    of the scan (``PERF.md`` section 4); the chip's 15.75 GiB hold both, and
    half a GiB more."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load("granite4h-micro-vp8")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 12544, SEQ_L, 0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params)) == 772_160_448
    rows = _sds(one_chip, (1, SEQ_L + 1), jnp.int32)
    try:
        compiled = _compile(step, params, on_chip(jax.eval_shape(opt_init, params)), rows, rows)
    finally:
        step.clear_cache()  # the job's own program object, kept by ``_programs``
    stats = _report("granite4h step", compiled)
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes <= 15.25 * 2**30
    assert cfg.mixers() == {"gqa": 1, "mamba2": 9}
    assert bb.mechanisms(cfg, SEQ_L) == {"ssd_scan": "pallas", "conv": "pallas"}
    assert _conv_under(compiled, "seq.ssm.conv") >= 3
    scan = [line for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and "seq.ssm.scan" in line]
    assert len(scan) == 9 * _SCAN_CALLS_A_LAYER


# -- and at the shapes of train-phi4flash-long8k: Phi-4-mini-flash widths, two
# Mamba-1 selective scans (5,120 channels on a state of 16), differential
# attention inside a window of 512, in full and as cross-attention, a gated
# memory unit, ONE row of 8,192 slots a step
@pytest.mark.parametrize("kind", ["pallas", "xla"])
def test_selective_scan_one_row_of_8k(one_chip, as_tpu, kind):
    """The selective scan alone, forward and backward, at the cell's shape:
    the kernel pair (the state in VMEM; what the backward pass keeps is the
    state entering every grid step, 10 MB) and XLA's loops (the control
    build's bfloat16 state takes them: a block's states are its backward
    pass's largest array). Neither keeps a state a slot (8,192 x 5,120 x 16
    floats would be 2.7 GB)."""
    from predictionio_tpu.ops import selscan

    low = {} if kind == "pallas" else dict(state_dtype=jnp.bfloat16, gate_dtype=jnp.bfloat16)
    assert selscan.scan_kind(5120, 16, SEQ_L, *low.values()) == kind

    def loss(x, dt, a, b, c, seg):
        with jax.named_scope("seq.mamba.scan"):
            return selscan.selective_scan(x, dt, a, b, c, seg, chunk=64, **low).sum()

    wide = _sds(one_chip, (1, SEQ_L, 5120), jnp.float32)
    state = _sds(one_chip, (1, SEQ_L, 16), jnp.float32)
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), _sds(one_chip, (1, SEQ_L, 5120), jnp.bfloat16),
        wide, _sds(one_chip, (5120, 16), jnp.float32), state, state,
        _sds(one_chip, (1, SEQ_L), jnp.int32))
    stats = _report(f"selective scan ({kind})", compiled)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "seq.mamba.scan" in line]
    # the kernel: forward and backward; its temporaries are the arrays laid
    # out as [L, 40, 128] and B's and C's cotangents lane by lane
    assert len(calls) == (2 if kind == "pallas" else 0)
    assert stats.temp_size_in_bytes < (0.6 if kind == "pallas" else 2) * 2**30


def test_windowed_attention_one_row_of_8k(one_chip):
    """Differential attention's core as one call of the blockwise loop (40
    member heads on 20 key heads of 64, values of 128) under the window of
    512, forward and backward; the window is in the loop's trip count: 31
    tiles where the full layer walks 136."""
    from predictionio_tpu.ops.attention import flash_attention

    def loss(q, k, v, seg, window):
        return flash_attention(q, k, v, causal=True, block_k=512, segment_ids=seg,
                               window=window).astype(jnp.float32).sum()

    avals = (_sds(one_chip, (1, 40, SEQ_L, 64), jnp.bfloat16),
             _sds(one_chip, (1, 20, SEQ_L, 64), jnp.bfloat16),
             _sds(one_chip, (1, 20, SEQ_L, 128), jnp.bfloat16), _sds(one_chip, (1, SEQ_L), jnp.int32))
    texts = {window: _compile(jax.grad(functools.partial(loss, window=window), argnums=(0, 1, 2)),
                              *avals).as_text() for window in (512, 0)}
    assert "s32[31]" in texts[512] and "s32[136]" not in texts[512]
    assert "s32[136]" in texts[0] and "s32[31]" not in texts[0]


def test_phi4flash_step_one_row_of_8k_fits_the_chip(one_chip, as_tpu):
    """The whole optimizer step of ``train-phi4flash-long8k`` (1 row of 8,193
    slots, 697 M parameters with their AdamW moments, donated) as the job
    compiles it: arguments 8.36 GB, temporaries 6.16 GB (6.50 before the scan's
    kernel: ``PERF.md`` section 4); the chip's 15.75 GiB hold both."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load("phi4-mini-flash-vp8")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 25008, SEQ_L, 0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params)) == 697_073_792
    rows = _sds(one_chip, (1, SEQ_L + 1), jnp.int32)
    try:
        compiled = _compile(step, params, on_chip(jax.eval_shape(opt_init, params)), rows, rows)
    finally:
        step.clear_cache()  # the job's own program object, kept by ``_programs``
    stats = _report("phi4flash step", compiled)
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes <= 15.75 * 2**30
    assert cfg.mixers() == {"cross": 1, "gmu": 1, "gqa": 1, "mamba1": 2, "swa": 1}
    assert bb.mechanisms(cfg, SEQ_L) == {
        "selective_scan": "pallas", "conv": "pallas", "attn_tiles_skipped_by_window": 105}
    # both Mamba-1 layers run the shared convolution kernel: forward, the
    # layer's recomputation, backward
    assert _conv_under(compiled, "seq.mamba.conv") >= 6
    text = compiled.as_text()
    # and the scan's kernel pair: forward and backward, and NO forward call in
    # the layer's recomputation (the layer keeps y and the states entering the
    # grid steps: ``kept`` in the table ``seq_backbone._MIXERS``). Temporaries 6.16 GB with what is
    # kept (5.74 GB without), under the 6.50 GB of XLA's loops (PERF.md section 4)
    scans = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "seq.mamba.scan" in line]
    assert len(scans) == 4
    assert stats.temp_size_in_bytes < 6.3e9
    assert "seq.attn.swa.core" in text and "seq.gmu" in text and "seq.attn.core" in text


@pytest.mark.parametrize("case", ["deltanet", "mamba2", "lfm2"])
def test_short_convolution_kernel_at_the_cells_shapes(one_chip, as_tpu, case):
    """The convolution chain alone, forward and backward, at the three
    callers' shapes (a row of 8,192 slots of the wide bfloat16 projection):
    the chip's compiler takes the tiles ``ops.shortconv._tile`` picks, the
    unaligned reads of the staged slots and the VMEM they ask for."""
    from predictionio_tpu.ops import shortconv as sc

    rows, width, channels, taps, pieces = {
        "deltanet": (1, 12288, 8192, 4, dict(silu=True)),
        "mamba2": (1, 8448, 4352, 4, dict(at=4096, silu=True, bias=True)),
        "lfm2": (2, 6144, 2048, 3, dict(at=4096, gate_in=0, gate_out=2048)),
    }[case]
    with_bias = pieces.pop("bias", False)

    def loss(src, w, bias, seg):
        y = sc.conv_chain(src, w, seg, channels=channels, bias=bias if with_bias else None,
                          interpret=False, **pieces)
        return jnp.sum(y * y)

    avals = (_sds(one_chip, (rows, SEQ_L, width), jnp.bfloat16),
             _sds(one_chip, (taps, channels), jnp.float32),
             _sds(one_chip, (channels,), jnp.float32), _sds(one_chip, (rows, SEQ_L), jnp.int32))
    for program in (loss, jax.grad(loss, argnums=(0, 1, 2))):
        assert 'custom_call_target="tpu_custom_call"' in _compile(program, *avals).as_text()


def test_joyai_step_has_no_short_convolution(one_chip, as_tpu):
    """``train-joyai-long8k``'s step runs no convolution: its lowered text
    names no ``seq.<mixer>.conv`` scope and its job counts no ``conv`` (the text's hash
    against the parent's is in ``CHANGES.md``)."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load("joyai-flash-48b-a3b-ep16")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 16160, SEQ_L, 0)))
    rows = _sds(one_chip, (2, SEQ_L + 1), jnp.int32)
    try:
        text = step.lower(params, on_chip(jax.eval_shape(opt_init, params)), rows, rows).as_text()
    finally:
        step.clear_cache()
    assert "tpu_custom_call" in text and not re.search(r"seq\.\w+\.conv", text)
    assert bb.mechanisms(cfg, SEQ_L) == {}



def test_chosen_core_one_row_of_16k(one_chip, as_tpu):
    """The sparse-attention core alone, forward and backward, at the cell's
    shape (32 heads of 128 on 4 key heads, one row of 16,384 slots, a mask of
    [16384, 16384]): ``chosen_attention`` is the kernel pair of
    ``ops/chosen_core.py``, two custom calls under the caller's scope (the
    backward one holds a key head's whole ``dk`` and ``dv`` in VMEM: 33.5 MB of
    its 100), and what XLA makes beside them is the mask a byte a pair (268 MB,
    once a pass), ``delta`` and the tables."""
    from predictionio_tpu.ops import chosen_core
    from predictionio_tpu.ops.attention import chosen_attention

    length = 16384
    assert chosen_core.core_kind(32, 4, 128, 128, length) == "pallas"

    def loss(q, k, v, chosen, seg):
        with jax.named_scope("seq.attn.core"):
            o, lse = chosen_attention(q, k, v, chosen, seg)
        return o.astype(jnp.float32).sum() + jax.lax.stop_gradient(lse).sum()

    heads = lambda n: _sds(one_chip, (1, n, length, 128), jnp.bfloat16)  # noqa: E731
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2)), heads(32), heads(4), heads(4),
        _sds(one_chip, (1, length, length), jnp.bool_), _sds(one_chip, (1, length), jnp.int32))
    stats = _report("chosen core", compiled)
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line and "seq.attn.core" in line]
    assert len(calls) == 2 and "chosen_core_forward" in calls[0] + calls[1]
    assert "chosen_core_backward" in calls[0] + calls[1]
    # the mask a byte a pair for each kernel, o and the cotangents; no float32 copy of q
    assert stats.temp_size_in_bytes < 1.0 * 2**30


def test_keye_step_one_row_of_16k_fits_the_chip(one_chip, as_tpu):
    """The whole optimizer step of ``train-keye-long16k`` (1 row of 16,385
    slots, six sparse-attention layers, 659 M parameters with their AdamW
    moments, donated) as the job compiles it. The chip's compiler takes it (it
    refuses a program that does not fit the chip's memory). With the cores on
    the XLA loop (PR 45) its buffer assignment read 15.04 GiB of the chip's
    15.75: arguments 7.91 GB, which the outputs alias, and 8.25 GB of
    temporaries, the gradient among them (``memory_analysis()`` counted 12.96
    GB of temporaries, the aliased outputs' share among them). With the cores
    on the kernel pair of ``ops/chosen_core.py`` (PR 46) ``memory_analysis()``
    counts 11.88 GB: the loop's three float32 carries of q's size go, a byte a
    pair of mask comes, and the step stands about 1.0 GiB lower, near 14.0 GiB.
    With the indexers' loss on the kernel pair of ``ops/indexer_kl.py`` (PR 48)
    it counts 11.92 GB: the loop's float32 carries (``d_iq`` [16384, 16, 64],
    ``d_ik``, ``d_iw`` and three [16384]) go, the kernels' own mask a byte a
    pair and the kept forward's two [16384] a layer come.
    The first reading decides how many layers the
    cut keeps (``conf/backbones/keye-vl2-30b-a3b-ep8.json``: six; five had it
    not fit); on the chip the step runs (``PERF.md`` section 4)."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    length = 16384
    cfg = bb.BackboneConfig.load("keye-vl2-30b-a3b-ep8")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 18992, length, 0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params)) == 659_190_016
    rows = _sds(one_chip, (1, length + 1), jnp.int32)
    try:
        compiled = _compile(step, params, on_chip(jax.eval_shape(opt_init, params)), rows, rows)
    finally:
        step.clear_cache()  # the job's own program object, kept by ``_programs``
    stats = _report("keye step", compiled)
    assert (stats.argument_size_in_bytes + stats.temp_size_in_bytes
            - stats.alias_size_in_bytes) <= 15.75 * 2**30
    assert cfg.mixers() == {"dsa": 6}
    assert bb.mechanisms(cfg, length) == {"chosen_core": "pallas", "index_kl": "pallas"}
    text = compiled.as_text()
    for scope in ("seq.attn.index", "seq.attn.select", "seq.attn.core", "seq.attn.index_loss"):
        assert scope in text
    # the core is the kernel pair: its custom calls lie under the core's scope
    core = [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and "seq.attn.core" in line]
    assert core and all("chosen_core_" in line for line in core)
    # and so is the indexers' loss: forward once (kept over the layer's recomputation) and
    # backward once a layer, under the loss's scope, with no loop over the tiles left there
    loss = [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and "seq.attn.index_loss" in line]
    assert sorted(line.split()[0].lstrip("%").split(".")[0] for line in loss) == [
        "index_kl_backward", "index_kl_forward"]
    assert not [line for line in text.splitlines()
                if " while(" in line and "seq.attn.index_loss" in line]


# -- and at the shapes of train-nemotron3nano-packed8k: Nemotron-3-Nano widths, four
# Mamba-2 layers (64 heads of 64 on a state of 128 in EIGHT groups, chunks of 128),
# four expert layers of ungated experts and one grouped-query attention layer (32
# heads on 2 of 128), every layer one part, TWO rows of 8,192 slots a step
@pytest.mark.parametrize("chunk", [128, 256])
def test_grouped_ssd_scan_two_rows_of_8k(one_chip, as_tpu, chunk):
    """The state-space scan alone with eight groups of B and C, forward and
    backward, at the cell's shape: a grid step's eight heads are one group,
    whose 128 columns of the 1,024 it is handed by block index; at the
    published chunk of 128 and at granite's 256."""
    from predictionio_tpu.ops import ssd

    assert ssd.scan_kind(64, 64, 128, SEQ_L, chunk, groups=8) == "pallas"
    assert ssd._tile(64, 64, 8) == 8

    def loss(u, dt, a, b, c, seg):
        with jax.named_scope("seq.ssm.scan"):
            return ssd.ssd_scan(u, dt, a, b, c, seg, chunk=chunk, groups=8,
                                compute_dtype=jnp.bfloat16).sum()

    state = _sds(one_chip, (2, SEQ_L, 8 * 128), jnp.bfloat16)
    compiled = _compile(
        jax.grad(loss, argnums=(0, 1, 2, 3, 4)), _sds(one_chip, (2, SEQ_L, 64, 64), jnp.bfloat16),
        _sds(one_chip, (2, SEQ_L, 64), jnp.float32), _sds(one_chip, (64,), jnp.float32),
        state, state, _sds(one_chip, (2, SEQ_L), jnp.int32))
    stats = _report(f"grouped state-space scan (chunks of {chunk})", compiled)
    text = compiled.as_text().splitlines()
    calls = [line for line in text
             if 'custom_call_target="tpu_custom_call"' in line and "seq.ssm.scan" in line]
    assert len(calls) == 2
    assert not [line for line in text if re.search(rf"= f32\[[\d,]*{chunk},{chunk}\]", line)]
    # y's cotangent, the states entering the chunks (2 MB a chunk and row) and what XLA
    # lays out a slot and head
    assert stats.temp_size_in_bytes < (1.0 if chunk == 128 else 0.8) * 2**30


def test_nemotronh_step_two_rows_of_8k_fits_the_chip(one_chip, as_tpu):
    """The whole optimizer step of ``train-nemotron3nano-packed8k`` (2 rows of
    8,193 slots, nine one-part layers, 667 M parameters with their AdamW
    moments, donated) as the job compiles it: the chip's compiler takes it,
    and arguments and temporaries together stay inside the chip's 15.75 GiB. The convolution (6,144 channels from column 4,096 of the
    10,240 the wide projection has) and the grouped scan are their kernels."""
    from predictionio_tpu.models import seq_backbone as bb
    from predictionio_tpu.models import sequencerec

    cfg = bb.BackboneConfig.load("nemotron3-nano-30b-a3b-ep16")
    opt_init, step, _ = sequencerec._programs(cfg, 3e-4, None, "auto")
    on_chip = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _sds(one_chip, s.shape, s.dtype), tree)
    params = on_chip(jax.eval_shape(lambda: bb.init_params(cfg, 16384, SEQ_L, 0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(params)) == 666_963_456
    rows = _sds(one_chip, (2, SEQ_L + 1), jnp.int32)
    try:
        compiled = _compile(step, params, on_chip(jax.eval_shape(opt_init, params)), rows, rows)
    finally:
        step.clear_cache()  # the job's own program object, kept by ``_programs``
    stats = _report("nemotronh step", compiled)
    # (read here: arguments 8.00 GB, which the outputs alias, temporaries 7.65 GB)
    assert stats.argument_size_in_bytes + stats.temp_size_in_bytes <= 15.75 * 2**30
    assert cfg.mixers() == {"gqa": 1, "mamba2": 4, "moe": 4}
    assert bb.mechanisms(cfg, SEQ_L) == {
        "ssd_scan": "pallas", "ssd_groups": 8, "expert_act": "relu2", "conv": "pallas"}
    assert _conv_under(compiled, "seq.ssm.conv") >= 3
    text = compiled.as_text()
    scan = [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line and "seq.ssm.scan" in line]
    assert len(scan) == 4 * _SCAN_CALLS_A_LAYER
    for scope in ("seq.moe.route", "seq.moe.experts", "seq.moe.shared", "seq.attn.core", "seq.head"):
        assert scope in text
