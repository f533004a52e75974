"""Attention kernels: blockwise (flash) and sequence-parallel (ring, Ulysses).

The reference has no sequence models at all (SURVEY §5 "Long-context /
sequence parallelism — absent"; its nearest neighbor is the MarkovChain
transition matrix, ``e2/.../MarkovChain.scala``). This framework treats
long-context as first-class: the sequence-recommendation engine
(:mod:`predictionio_tpu.models.sequencerec`) and any future sequence model
train over context windows sharded across the mesh ``seq`` axis.

Three schedules, one math:

- :func:`flash_attention` — single-device blockwise attention with an online
  softmax (``lax.scan`` over KV blocks): O(block²) memory instead of O(L²),
  XLA fuses the inner matmuls onto the MXU.
- :func:`ring_attention` — sequence parallelism over a mesh axis: every
  device keeps its Q chunk, KV chunks rotate around the ring via
  ``ppermute`` (ICI neighbor exchanges), partial results merge with the same
  online-softmax rescaling. Peak memory per device is O(L²/N²) score tiles;
  communication overlaps compute chunk by chunk.
- :func:`ulysses_attention` — all-to-all alternative: resharding seq→heads
  before attention and heads→seq after, so each device runs *full-sequence*
  attention for a subset of heads. Two all-to-alls instead of N-1 ring
  hops — better when heads ≥ devices and ICI all-to-all bandwidth is good.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from jax.experimental import pallas as pl

SEQ_AXIS = "seq"

_NEG_BIG = -1e30  # additive mask value (finite: keeps fully-masked rows NaN-free)


def _attend_block(q, k, v, m, l, o, mask, scale):
    """One online-softmax accumulation step.

    q [..., Lq, D], k/v [..., Lk, D]; running (m, l, o) with m/l [..., Lq]
    and o [..., Lq, D]; ``mask`` is an optional [Lq, Lk] bool (True = keep).
    """
    scores = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, _NEG_BIG)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * correction + p.sum(axis=-1)
    o_new = o * correction[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, o_new


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, blk_q, blk_k, lk,
                  causal, scale, n_kv):
    """One (batch·head, Q-block) grid step: online softmax over KV blocks.

    Everything lives in VMEM: q block [blk_q, D], full K/V [Lk_pad, D]
    (fetched once per batch·head — the Q-block grid dim is innermost and
    their index map is constant in it), score tiles [blk_q, blk_k] that
    never touch HBM — the O(L²) score matrix is the thing this kernel
    exists to not materialize.
    """
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # [blk_q, D]
    d = q.shape[-1]
    q_pos = i * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0
    )

    def step(j, carry):
        m, l, o = carry
        kj = k_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        vj = v_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kj, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [blk_q, blk_k]
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1
        )
        keep = k_pos < lk
        if causal:
            keep = keep & (q_pos >= k_pos)
        s = jnp.where(keep, s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * corr + p.sum(axis=1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p, vj, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, o_new

    m0 = jnp.full((blk_q,), _NEG_BIG, dtype=jnp.float32)
    l0 = jnp.zeros((blk_q,), dtype=jnp.float32)
    o0 = jnp.zeros((blk_q, d), dtype=jnp.float32)
    # causal: KV blocks strictly above this Q block's diagonal contribute
    # nothing — skip them (the classic flash-attention work saving)
    hi = (
        jnp.minimum(((i + 1) * blk_q + blk_k - 1) // blk_k, n_kv)
        if causal else n_kv
    )
    m, l, o = jax.lax.fori_loop(0, hi, step, (m0, l0, o0))
    o_ref[0] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "blk_q", "blk_k", "interpret")
)
def _flash_pallas_call(q, k, v, causal, blk_q, blk_k, interpret):
    b, h, lq, d = q.shape
    lk = k.shape[2]
    lq_pad = -lq % blk_q
    lk_pad = -lk % blk_k
    if lq_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad), (0, 0)))
    if lk_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad), (0, 0)))
    bh = b * h
    qr = q.reshape(bh, lq + lq_pad, d)
    kr = k.reshape(bh, lk + lk_pad, d)
    vr = v.reshape(bh, lk + lk_pad, d)
    n_kv = (lk + lk_pad) // blk_k
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, blk_q=blk_q, blk_k=blk_k, lk=lk,
            causal=causal, scale=1.0 / np.sqrt(d), n_kv=n_kv,
        ),
        grid=(bh, (lq + lq_pad) // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda bhi, i: (bhi, i, 0)),
            pl.BlockSpec((1, lk + lk_pad, d), lambda bhi, i: (bhi, 0, 0)),
            pl.BlockSpec((1, lk + lk_pad, d), lambda bhi, i: (bhi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda bhi, i: (bhi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq + lq_pad, d), q.dtype),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, lq + lq_pad, d)[:, :, :lq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_pallas_diff(q, k, v, causal, blk_q, blk_k, interpret):
    return _flash_pallas_call(q, k, v, causal, blk_q, blk_k, interpret)


def _flash_pallas_fwd(q, k, v, causal, blk_q, blk_k, interpret):
    # flash-style backward: save only q/k/v and recompute attention in
    # the VJP (the O(L²) score matrix is never a residual) — here the
    # recompute runs through the XLA online-softmax path, whose autodiff
    # is the reference math the kernel is equality-tested against
    return (
        _flash_pallas_call(q, k, v, causal, blk_q, blk_k, interpret),
        (q, k, v),
    )


def _flash_pallas_bwd(causal, blk_q, blk_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=causal),
        q, k, v,
    )
    return vjp(g)


_flash_pallas_diff.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


def flash_attention_pallas(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Pallas flash attention: fused scores+softmax+PV per Q block, causal
    upper-triangle KV blocks skipped entirely. K/V are VMEM-resident per
    batch·head, so this single-device kernel targets L up to the VMEM
    budget (~16k at D=64); beyond that, shard the sequence (ring/Ulysses
    — which is the framework's long-context answer anyway).

    Differentiable: a custom VJP recomputes attention through the XLA
    online-softmax path in the backward pass (flash-style — only q/k/v
    are residuals, never the score matrix), so training through this
    kernel is supported.

    EXPERIMENTAL: selected via ``attention(..., impl="pallas")`` /
    ``flash_impl`` in sequencerec params, XLA path remains the default
    until the Mosaic lowering is hardware-validated (``flash_pallas``
    step in the revalidation queue). ``interpret=None`` auto-selects the
    interpreter off-TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    lq, lk = q.shape[2], k.shape[2]
    return _flash_pallas_diff(
        q, k, v, causal, min(block_q, max(8, lq)), min(block_k, lk),
        interpret,
    )


@functools.partial(jax.jit, static_argnames=("causal", "block_k"))
def flash_attention(
    q: jax.Array,  # [B, H, L, D]
    k: jax.Array,  # [B, H, L, D]
    v: jax.Array,  # [B, H, L, D]
    causal: bool = True,
    block_k: int = 512,
) -> jax.Array:
    """Blockwise attention with online softmax (single device)."""
    b, h, lq, d = q.shape
    lk = k.shape[2]
    scale = 1.0 / np.sqrt(d)
    blk = min(block_k, lk)
    n_blocks = (lk + blk - 1) // blk
    pad = n_blocks * blk - lk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))

    q_pos = jnp.arange(lq)
    kb = k.reshape(b, h, n_blocks, blk, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, n_blocks, blk, d).transpose(2, 0, 1, 3, 4)

    qf = q.astype(jnp.float32)

    def step(carry, inputs):
        m, l, o = carry
        (j, kj, vj) = inputs
        k_pos = j * blk + jnp.arange(blk)
        valid = k_pos < lk  # padded keys masked out
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :]) & valid[None, :]
        else:
            mask = jnp.broadcast_to(valid[None, :], (lq, blk))
        m, l, o = _attend_block(
            qf, kj.astype(jnp.float32), vj, m, l, o, mask, scale
        )
        return (m, l, o), None

    m0 = jnp.full((b, h, lq), _NEG_BIG, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, lq), dtype=jnp.float32)
    o0 = jnp.zeros((b, h, lq, d), dtype=jnp.float32)
    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0), (jnp.arange(n_blocks), kb, vb)
    )
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def ring_attention(
    q: jax.Array,  # [B, H, L, D] — L sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = SEQ_AXIS,
    causal: bool = True,
) -> jax.Array:
    """Sequence-parallel attention: KV chunks rotate around the mesh ring.

    Inputs/outputs are length-sharded over ``axis`` (chunk i on device i,
    contiguous order). Each of the N ring steps attends the local Q chunk to
    the visiting KV chunk with global-position causal masking, merging via
    online-softmax rescaling; ``ppermute`` moves KV to the next neighbor —
    N-1 ICI hops, never materializing more than one remote chunk.
    """
    n = mesh.shape[axis]
    b, h, l, d = q.shape
    assert l % n == 0, f"sequence length {l} not divisible by ring size {n}"
    chunk = l // n
    scale = 1.0 / np.sqrt(d)

    def local(qc, kc, vc):
        # qc/kc/vc: [B, H, chunk, D] local shards
        my = jax.lax.axis_index(axis)
        q_pos = my * chunk + jnp.arange(chunk)
        qf = qc.astype(jnp.float32)

        def step(s, carry):
            m, l_, o, kc_, vc_ = carry
            src = (my - s) % n  # owner of the currently-visiting KV chunk
            k_pos = src * chunk + jnp.arange(chunk)
            mask = (q_pos[:, None] >= k_pos[None, :]) if causal else None
            m, l_, o = _attend_block(
                qf, kc_.astype(jnp.float32), vc_, m, l_, o, mask, scale,
            )
            perm = [(i, (i + 1) % n) for i in range(n)]
            kc_ = jax.lax.ppermute(kc_, axis, perm)
            vc_ = jax.lax.ppermute(vc_, axis, perm)
            return m, l_, o, kc_, vc_

        m0 = jnp.full((b, h, chunk), _NEG_BIG, dtype=jnp.float32)
        l0 = jnp.zeros((b, h, chunk), dtype=jnp.float32)
        o0 = jnp.zeros((b, h, chunk, d), dtype=jnp.float32)
        m, l_, o, _, _ = jax.lax.fori_loop(
            0, n, step, (m0, l0, o0, kc, vc)
        )
        return (o / jnp.maximum(l_, 1e-30)[..., None]).astype(qc.dtype)

    spec = P(None, None, axis, None)
    f = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return jax.jit(f)(q, k, v)


def ulysses_attention(
    q: jax.Array,  # [B, H, L, D] — L sharded over `axis`
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = SEQ_AXIS,
    causal: bool = True,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses schedule):
    reshard seq→heads, full-sequence attention per head subset, reshard
    heads→seq. Requires ``H % mesh.shape[axis] == 0``."""
    n = mesh.shape[axis]
    b, h, l, d = q.shape
    assert h % n == 0, f"{h} heads not divisible by {n} devices"
    assert l % n == 0, f"sequence length {l} not divisible by {n} devices"

    def local(qc, kc, vc):
        # [B, H, L/N, D] → all-to-all → [B, H/N, L, D]
        def a2a_in(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=1, concat_axis=2, tiled=True
            )

        def a2a_out(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=2, concat_axis=1, tiled=True
            )

        qh, kh, vh = a2a_in(qc), a2a_in(kc), a2a_in(vc)
        oh = flash_attention(qh, kh, vh, causal=causal)
        return a2a_out(oh)

    spec = P(None, None, axis, None)
    f = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return jax.jit(f)(q, k, v)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Optional[Mesh] = None,
    axis: str = SEQ_AXIS,
    causal: bool = True,
    schedule: str = "auto",
    impl: str = "xla",
) -> jax.Array:
    """Dispatch: single-device flash when no mesh / 1-device axis; otherwise
    ring (default) or Ulysses (``schedule="ulysses"``, when heads divide).
    ``impl="pallas"`` selects the fused single-device kernel
    (:func:`flash_attention_pallas`; experimental, hardware-gated) —
    sharded schedules keep the XLA inner step for now."""
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if mesh is None or axis not in mesh.shape or mesh.shape[axis] == 1:
        if impl == "pallas":
            return flash_attention_pallas(q, k, v, causal=causal)
        return flash_attention(q, k, v, causal=causal)
    if schedule == "ulysses":
        return ulysses_attention(q, k, v, mesh, axis, causal)
    if schedule not in ("auto", "ring"):
        raise ValueError(f"unknown attention schedule {schedule!r}")
    return ring_attention(q, k, v, mesh, axis, causal)
