"""Serving-side scoring kernels.

The hot path of the deployed recommendation engine: the reference scores via
``MatrixFactorizationModel.recommendProducts`` (factor dot products, invoked
per query in ``examples/.../ALSAlgorithm.scala:76-80``); here queries are
batched into one gather → matmul → top-k device call
(SURVEY §3.2 "batched gather-dot kernel").

All kernels are jit'd with static k so repeated serving calls hit the
compilation cache; the query batch rides the mesh ``data`` axis when the
server shards a batch across chips.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..quant.ragged import ragged_gather

# plain float, NOT jnp.float32(...): a module-level jnp scalar would
# initialize the device backend at import time (slow start-up for every
# CLI invocation, and a hang if the accelerator is unreachable)
NEG_INF = float("-inf")


#: "auto" streams on TPU once the would-be ``[B, I]`` score matrix
#: exceeds this many bytes (64 MB). Below it the XLA dense path wins:
#: the matrix fits comfortably and the streaming kernel's unrolled
#: k-pass extraction costs k sweeps per tile. Above it the dense path's
#: HBM write+read of the score matrix is the serving bandwidth bill the
#: fused kernel removes — the round-12 default-flip lowered the bar
#: from 1 GB ("only when mandatory") to this ("whenever it wins").
STREAMING_TOPK_BYTES = 1 << 26


def use_streaming_topk(mode: str, b_pad: int, n_items: int) -> bool:
    """Shared streaming-top-k selection rule for serving templates.

    Streaming (``pallas_kernels.top_k_streaming``) keeps the ``[B, I]``
    score matrix out of HBM entirely. "auto" switches at
    :data:`STREAMING_TOPK_BYTES` of would-be scores on TPU (the XLA
    dense path is faster below that and the interpret-mode kernel is
    slow off-TPU, where the fused entry points fall back to XLA
    ``lax.top_k``). Raises on an unknown mode so a config typo fails at
    validation time, not mid-serving.
    """
    if mode not in ("auto", "always", "never"):
        raise ValueError(
            f"streaming_top_k must be 'auto', 'always' or 'never', "
            f"got {mode!r}"
        )
    if mode == "never":
        return False
    if mode == "always":
        return True
    import jax

    return (
        jax.default_backend() == "tpu"
        and b_pad * n_items * 4 > STREAMING_TOPK_BYTES
    )


def pad_pow2(n: int, lo: int = 1) -> int:
    """Smallest power of two >= max(n, lo).

    Serving shape-bucketing: micro-batched query batches arrive at every
    size from 1 to batch_max; dispatching each size directly would compile
    a fresh XLA program per size (20-40 s each on TPU). Padding batch and
    k to powers of two bounds the compile set to O(log) shapes."""
    n = max(int(n), lo)
    return 1 << (n - 1).bit_length()


#: Serving scores are f32 scores. On a TPU the default matmul precision
#: rounds f32 inputs to bf16 passes: measured on the v5e (PR 22) that put
#: served scores 7e-3 away from the f32 dot product and swapped
#: neighbours up to 5e-3 apart in 14 of 32 top-10 lists — a ranking that
#: differs from what the same model answers on any other backend.
#: HIGHEST restores the f32 product; the score matmul is a small part of
#: a dispatch. The streaming kernel names the same precision.
SCORE_PRECISION = jax.lax.Precision.HIGHEST


def _score_topk(query_vectors, item_factors, k, exclude_mask):
    scores = jnp.einsum(
        "br,ir->bi", query_vectors, item_factors,
        preferred_element_type=jnp.float32, precision=SCORE_PRECISION,
    )
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask, NEG_INF, scores)
    return jax.lax.top_k(scores, k)


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_for_users(
    user_factors: jax.Array,  # [U, R]
    item_factors: jax.Array,  # [I, R]
    user_idx: jax.Array,  # [B] int32
    k: int,
    exclude_mask: Optional[jax.Array] = None,  # [B, I] bool — True = exclude
) -> Tuple[jax.Array, jax.Array]:
    """Top-k items for a batch of known users.

    Returns (scores [B, k], item indices [B, k]). ``exclude_mask`` implements
    the seen/unavailable-item filters the e-commerce template applies
    (reference ``ALSAlgorithm.scala`` in ecommerce template).
    """
    return _score_topk(user_factors[user_idx], item_factors, k, exclude_mask)


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_for_vectors(
    query_vectors: jax.Array,  # [B, R]
    item_factors: jax.Array,  # [I, R]
    k: int,
    exclude_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Top-k items for raw query vectors (cold-start / feature queries)."""
    return _score_topk(query_vectors, item_factors, k, exclude_mask)


@functools.partial(jax.jit, static_argnames=("k", "exclude_self"))
def top_k_similar_items(
    item_factors: jax.Array,  # [I, R]
    item_idx: jax.Array,  # [B] int32
    k: int,
    exclude_self: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Cosine-similar items — the similarproduct template's kernel
    (reference: ALS ``productFeatures`` cosine,
    ``examples/scala-parallel-similarproduct``).

    Returns (cosine scores [B, k], item indices [B, k]); when
    ``exclude_self`` the query item's own score is masked to -inf before
    the top-k selection.
    """
    norms = jnp.linalg.norm(item_factors, axis=1, keepdims=True)
    unit = item_factors / jnp.maximum(norms, 1e-12)
    q = unit[item_idx]  # [B, R]
    scores = jnp.einsum(
        "br,ir->bi", q, unit,
        preferred_element_type=jnp.float32, precision=SCORE_PRECISION,
    )
    if exclude_self:
        n_items = item_factors.shape[0]
        one_hot = jax.nn.one_hot(item_idx, n_items, dtype=jnp.bool_)
        scores = jnp.where(one_hot, NEG_INF, scores)
    return jax.lax.top_k(scores, k)


# -- fused score+select top-k (docs/performance.md#levers) ------------------
#
# One serving entry point per query kind that never materializes the
# [B, I] score matrix when the backend can avoid it: on TPU (when
# use_streaming_topk says streaming wins) the Pallas streaming kernel
# folds each item tile's scores into a VMEM-resident running top-k; off
# TPU (or below the streaming bar) an XLA score + lax.top_k fallback
# with the SAME result contract. Both paths keep the factor tables
# device-resident and return only [B, k] to the host. Exactness vs the
# dense kernels is pinned in tests/test_als.py::TestFusedTopK — same
# items, same order, scores to f32 reassociation tolerance (the
# fleet/merge.py merged_matches_reference contract).
#
# Sentinel contract (inherited from top_k_streaming, BOTH paths): a slot
# with fewer than k valid candidates holds score -inf and index -1 —
# callers must treat -1 as absent, never index with it.


def xla_topk_with_sentinels(
    query_vectors: jax.Array,
    item_factors: jax.Array,
    k: int,
    exclude_idx: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """The dense XLA leg of the fused top-k: dense score + ``lax.top_k``
    normalized to the streaming kernel's sentinel contract (-inf / -1 on
    invalid slots, k padded past the catalog size). Index-list exclusions
    (``[B, E]`` int32, -1 padded) densify to a one-hot mask here — the
    dense path pays the [B, I] bytes anyway."""
    n_items = item_factors.shape[0]
    k_eff = min(k, n_items)
    mask = None
    if exclude_idx is not None and exclude_idx.shape[1] > 0:
        excl = jnp.asarray(exclude_idx, jnp.int32)
        one_hot = jax.nn.one_hot(
            jnp.where(excl >= 0, excl, n_items), n_items + 1,
            dtype=jnp.bool_,
        ).any(axis=1)[:, :n_items]
        mask = one_hot
    scores, idx = top_k_for_vectors(
        query_vectors, item_factors, k_eff, exclude_mask=mask
    )
    # any -inf slot (excluded/invalid) carries the -1 index sentinel,
    # never a real (excluded) item id
    idx = jnp.where(jnp.isneginf(scores), -1, idx)
    if k_eff < k:
        scores = jnp.pad(
            scores, ((0, 0), (0, k - k_eff)), constant_values=NEG_INF
        )
        idx = jnp.pad(idx, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return scores, idx


def resolve_topk_path(mode: str, b: int, n_items: int) -> str:
    """The resolved serve-side top-k path — "streaming" (Pallas fused
    kernel) or "dense" (XLA score + ``lax.top_k``). The ONE decision
    home: :func:`top_k_fused_vectors` dispatches on it and the serving
    templates record it (``/status.json`` → ``topkPath``), so the
    reported path can never drift from the executed one."""
    return "streaming" if use_streaming_topk(mode, b, n_items) else "dense"


def _fused_dispatch(query_vectors, item_factors, k, exclude_idx, mode):
    """Shared dispatch body of the fused entries (all jitted — the
    path decision and the streaming kernel's padding logic run at trace
    time, so a serving batch stays ONE device program like the dense
    kernels it replaces)."""
    path = resolve_topk_path(
        mode, query_vectors.shape[0], item_factors.shape[0]
    )
    if path == "streaming":
        from .pallas_kernels import top_k_streaming

        return top_k_streaming(query_vectors, item_factors, k, exclude_idx)
    return xla_topk_with_sentinels(
        query_vectors, item_factors, k, exclude_idx
    )


@functools.partial(jax.jit, static_argnames=("k", "mode"))
def top_k_fused_vectors(
    query_vectors: jax.Array,  # [B, R]
    item_factors: jax.Array,  # [I, R]
    k: int,
    exclude_idx: Optional[jax.Array] = None,  # [B, E] int32, -1 padded
    mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Fused score+select for raw query vectors. ``mode`` is the
    template-level ``streaming_top_k`` knob ("auto" | "always" |
    "never"), static like ``k`` so repeated serving calls hit the
    compilation cache."""
    return _fused_dispatch(query_vectors, item_factors, k, exclude_idx,
                           mode)


@functools.partial(jax.jit, static_argnames=("k", "mode"))
def top_k_for_users_fused(
    user_factors: jax.Array,  # [U, R]
    item_factors: jax.Array,  # [I, R]
    user_idx: jax.Array,  # [B] int32
    k: int,
    exclude_idx: Optional[jax.Array] = None,
    mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Fused top-k items for known users (the recommendation template's
    serving kernel): user-row gather stays on device inside the same
    program, and exclusions are per-query index lists instead of a
    dense ``[B, I]`` mask. The gather rides ``quant.ragged_gather`` —
    duplicate users in a batch (hot users under load) read their factor
    row once; bit-identical to the dense ``table[idx]`` it replaced."""
    return _fused_dispatch(
        ragged_gather(user_factors, user_idx),
        item_factors, k, exclude_idx, mode,
    )


@functools.partial(jax.jit, static_argnames=("k", "exclude_self", "mode"))
def top_k_similar_items_fused(
    item_factors: jax.Array,  # [I, R]
    item_idx: jax.Array,  # [B] int32
    k: int,
    exclude_self: bool = True,
    mode: str = "auto",
) -> Tuple[jax.Array, jax.Array]:
    """Fused cosine-similar items (the similarproduct kernel): the
    catalog normalization fuses into the same program (as the dense
    kernel's always did) and the query item's own index rides the
    streaming kernel's exclusion list — a [B, 1] index list instead of
    the dense ``[B, I]`` one-hot the unfused kernel builds. Note the
    sentinel contract difference from ``top_k_similar_items``: a sub-k
    slot here is (-inf, -1), not a real index with a -inf score."""
    item_factors = jnp.asarray(item_factors)
    norms = jnp.linalg.norm(item_factors, axis=1, keepdims=True)
    unit = item_factors / jnp.maximum(norms, 1e-12)
    idx = jnp.asarray(item_idx, jnp.int32)
    excl = idx[:, None] if exclude_self else None
    return _fused_dispatch(ragged_gather(unit, idx), unit, k, excl, mode)


def estimate_topk_hbm_bytes(
    b: int, n_items: int, rank: int, k: int, streaming: bool
) -> float:
    """HBM-traffic model for one batched top-k dispatch — the serve-side
    companion of ``ops.als.estimate_iteration_hbm_bytes`` (honest
    roofline accounting for the fused path, docs/performance.md#levers).

    Dense (XLA) path: read both factor inputs once, WRITE the [B, I]
    score matrix, re-read it for ``lax.top_k``, write [B, k] results
    (scores f32 + indices i32). Streaming path: the score tile lives in
    VMEM, so the matrix never touches HBM — item factors stream through
    once, queries and results are the only other traffic. Pinned by
    ``tests/test_als.py::TestTopkBytesModel``."""
    factors = float(b) * rank * 4.0 + float(n_items) * rank * 4.0
    results = float(b) * k * 8.0
    if streaming:
        return factors + results
    score_matrix = float(b) * n_items * 4.0
    return factors + 2.0 * score_matrix + results


@jax.jit
def standardize(scores: jax.Array) -> jax.Array:
    """Z-score standardization — the multi-algorithm ensemble combine step
    (reference similarproduct ``multi/`` Serving z-score + sum)."""
    mean = jnp.mean(scores)
    std = jnp.std(scores)
    return (scores - mean) / jnp.maximum(std, 1e-12)


# -- jit boundary telemetry (docs/observability.md#profiling) ---------------
#
# The serving dispatch is where a retrace hurts most: an unexpected
# shape reaching one of these kernels costs a fresh XLA compile inside a
# live request's latency budget (pad_pow2 exists to prevent exactly
# that). Routing every call through the process jit telemetry makes a
# pad_pow2 regression visible as pio_jit_retraces_total{fn=...} on the
# query server's /metrics instead of as an unexplained p99 cliff. The
# wrappers forward attributes, so `.lower()`-style AOT use keeps working.
from ..obs.profile import default_telemetry as _default_telemetry

top_k_for_users = _default_telemetry().wrap(
    "serving.topk_users", top_k_for_users
)
top_k_for_vectors = _default_telemetry().wrap(
    "serving.topk_vectors", top_k_for_vectors
)
top_k_similar_items = _default_telemetry().wrap(
    "serving.topk_similar", top_k_similar_items
)
top_k_fused_vectors = _default_telemetry().wrap(
    "serving.topk_fused", top_k_fused_vectors
)
top_k_for_users_fused = _default_telemetry().wrap(
    "serving.topk_users_fused", top_k_for_users_fused
)
top_k_similar_items_fused = _default_telemetry().wrap(
    "serving.topk_similar_fused", top_k_similar_items_fused
)
standardize = _default_telemetry().wrap("serving.standardize", standardize)
