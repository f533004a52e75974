"""Alternating Least Squares on TPU.

The compute-plane replacement for the reference's delegation to Spark MLlib
``ALS.train`` (invoked from the recommendation templates, e.g.
``examples/scala-parallel-recommendation/custom-prepartor/src/main/scala/
ALSAlgorithm.scala:56-62``; SURVEY §2.8 maps MLlib's block-partitioned factors
to mesh-sharded factor tables).

Semantics follow MLlib 1.2's explicit-feedback ALS (ALS-WR): per-row normal
equations ``(Yᵀ_u Y_u + λ·n_u·I) x_u = Yᵀ_u r_u`` with the regularizer scaled
by the row's rating count, and the implicit-preference variant (Hu-Koren-
Volinsky) with confidence ``c = 1 + α·r`` using the precomputed global
``YᵀY``.

TPU mapping
-----------
Ratings are CSR-like, grouped into **degree buckets** (ALX, arXiv:2112.02194):
every row in a bucket is padded to the bucket's width K, so each bucket is a
dense ``[B, K]`` problem — static shapes for XLA, gathers + batched matmuls on
the MXU, batched Cholesky solves. A Python loop over buckets issues a few
jit-compiled shapes; inside a bucket, rows stream through fixed-size blocks.

Sharding: the row dimension (users or items being solved) is sharded over the
mesh ``data`` axis; the opposite factor table is replicated (all-gathered by
XLA when the side switches). For factor tables too big to replicate, pass a
``model``-sharded table and XLA turns the gather into an all-to-all — the
mesh layout, not this code, decides the collective pattern.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import span

#: Default degree-bucket widths; a row pads to the nearest at or above its
#: degree. Powers of 2 up to 32, powers of 4 from there. The ladder is fine
#: only under the usual ranks: a narrow bucket gathers its rows in XLA at a
#: fixed price per padded slot, and that is where most rows of a
#: recommendation job are (one or two ratings each), so padding them to 8
#: gathered four slots for every rating. From 128 up a finer rung buys
#: nothing: ``gramian_fused`` pads a bucket's width to a multiple of 128.
DEFAULT_BUCKET_WIDTHS = (1, 2, 4, 8, 16, 32, 128, 512, 2048, 8192, 32768)

#: The rungs the device scopes group buckets by (the ladder before it
#: grew its narrow widths): a bucket runs under ``als.w<rung>`` of the
#: smallest rung at or above its width, so ``als.w8`` is every row of at
#: most 8 ratings whatever it is padded to (docs/observability.md).
_SCOPE_RUNGS = (8, 32, 128, 512, 2048, 8192, 32768)

#: Most rows a device block may hold at any width: on the Pallas path a
#: block's normal equations are ``[R, R, B]`` whatever the width (205 MB
#: at rank 50), so under the rank it is they, not the gather, that bound
#: the block. (An explicit job solves those blocks as ``[k, k, B]``
#: systems; an implicit one still builds ``[R, R, B]``.)
_MAX_BLOCK_ROWS = 16384

#: Max rows per device block inside a bucket solve (bounds peak gather
#: memory). Small buckets allocate LESS than a full block — see
#: :func:`_alloc_block`: sentinel padding rows cost real device FLOPs.
_BLOCK_ROWS = {
    1: 16384, 2: 16384, 4: 16384, 8: 16384, 16: 16384, 32: 8192,
    128: 4096, 512: 1024, 2048: 256, 8192: 64, 32768: 16,
}


@dataclasses.dataclass
class Bucket:
    """One padded degree bucket: ``rows[i]`` has its ratings in
    ``idx/val[i, :counts[i]]``.

    When built with ``pad_to_blocks=True`` the bucket additionally carries
    whole padding rows (``rows == n_rows`` sentinel, ``counts == 0``) so
    :func:`stage` can ship the slabs without re-padding copies.
    """

    rows: np.ndarray  # [B] int32 — row ids in the full matrix
    idx: np.ndarray  # [B, K] int32/uint16 — column indices (0-padded)
    val: np.ndarray  # [B, K] float32 — ratings (0-padded)
    counts: np.ndarray  # [B] int32 — valid entries per row (<= K)

    @property
    def width(self) -> int:
        return self.idx.shape[1]

    @property
    def mask(self) -> np.ndarray:
        """[B, K] float32 validity mask, derived on demand — ratings are
        prefix-packed, so the mask is a pure function of ``counts``."""
        return (
            np.arange(self.width, dtype=np.int32)[None, :]
            < self.counts[:, None]
        ).astype(np.float32)


@dataclasses.dataclass
class BucketedMatrix:
    """One side of the rating matrix (by-row = by-user or by-item)."""

    n_rows: int
    n_cols: int
    nnz: int
    buckets: List[Bucket]


def bucketize(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    bucket_widths: Sequence[int] = DEFAULT_BUCKET_WIDTHS,
    pad_to_blocks: bool = False,
) -> BucketedMatrix:
    """COO → degree-bucketed padded CSR.

    Rows with degree above the largest width are truncated to it (keeping
    the first ratings in input order) — with the default widths this only
    triggers beyond 32768 ratings per row.

    ``pad_to_blocks=True`` allocates each bucket's slabs rounded up to the
    device chunk size (``_BLOCK_ROWS``) with sentinel padding rows, so
    :func:`stage` ships them zero-copy — the training fast path. Column
    indices are uint16 whenever ``n_cols`` fits (half the transfer bytes).

    Dispatches to the native (C++ threaded O(nnz) scatter,
    ``native/bucketize.cc``) or the numpy (argsort-based) implementation;
    both produce bit-identical arrays. ``PIO_NO_NATIVE_BUCKETIZE=1`` forces
    the numpy path; a missing toolchain falls back silently.
    """
    nnz = len(rows)
    if nnz >= 2**31 or n_rows >= 2**31 or n_cols >= 2**31:
        raise ValueError("bucketize supports up to 2^31-1 ratings/ids")
    rows = np.ascontiguousarray(np.asarray(rows), dtype=np.int32)
    cols = np.ascontiguousarray(np.asarray(cols), dtype=np.int32)
    vals = np.ascontiguousarray(np.asarray(vals), dtype=np.float32)
    import os as _os

    global _NATIVE_BUCKETIZE_BROKEN
    if (
        nnz
        and not _NATIVE_BUCKETIZE_BROKEN
        and _os.environ.get("PIO_NO_NATIVE_BUCKETIZE") != "1"
    ):
        from ..native import NativeBuildError

        try:
            return _bucketize_native(
                rows, cols, vals, n_rows, n_cols, bucket_widths,
                pad_to_blocks,
            )
        except NativeBuildError as exc:
            # Toolchain-less host: numpy is full parity. Cache the verdict
            # so we don't re-spawn a doomed compiler on every call; any
            # OTHER failure propagates — a native-path bug must not become
            # a silent slowdown.
            import logging

            logging.getLogger(__name__).warning(
                "native bucketize unavailable, using numpy path: %s", exc
            )
            _NATIVE_BUCKETIZE_BROKEN = True
    return _bucketize_numpy(
        rows, cols, vals, n_rows, n_cols, bucket_widths, pad_to_blocks
    )


#: Set after the first failed native-bucketize build (per process).
_NATIVE_BUCKETIZE_BROKEN = False


def _idx_dtype(n_cols: int):
    """Staged column-index dtype: uint16 when the opposite-side id space
    fits (halves the largest slab's bytes), else int32. Single source of
    truth for bucketize (both paths), stage, and the C++ fill's
    caller-guarantee."""
    return np.uint16 if n_cols <= 0xFFFF else np.int32


def _alloc_block(width: int, n_real: int) -> int:
    """Row-allocation granularity for one bucket: the smaller of the
    width's :data:`_BLOCK_ROWS` bound (peak gather memory) and the
    power-of-two envelope of the bucket's real row count (floor 8, the
    sublane granularity).

    Sentinel padding rows are not free — the solve einsums compute over
    them — and allocating a FULL device block regardless of occupancy
    made small workloads mostly padding: at the bench's CPU-fallback
    scale the widest buckets carried 1–7 real rows in 16–64-row blocks
    (74–99% wasted FLOPs, measured round 12). Right-sizing to a power
    of two keeps the compiled-program set O(log) per width (the serving
    ``pad_pow2`` discipline) while the block bound still caps the
    gather working set for full buckets."""
    block = _block_rows_for(int(width))
    if n_real <= 0:
        return block
    pow2 = 1 << (max(int(n_real), 8) - 1).bit_length()
    return min(block, pow2)


def _alloc_rows(sel, counts_clip, n_rows, width, pad_to_blocks):
    """Rows/counts arrays for one bucket, optionally rounded up to the
    device chunk size with (n_rows, 0) sentinel padding rows. Empty
    buckets stay empty (they are dropped later; padding them would zero a
    whole block-sized slab for nothing)."""
    b = len(sel)
    if not pad_to_blocks or b == 0:
        return sel, counts_clip, b
    block = _alloc_block(int(width), b)
    b_alloc = -(-b // block) * block
    rows_arr = np.full(b_alloc, n_rows, dtype=np.int32)
    rows_arr[:b] = sel
    cnt = np.zeros(b_alloc, dtype=np.int32)
    cnt[:b] = counts_clip
    return rows_arr, cnt, b_alloc


def _bucketize_native(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    bucket_widths: Sequence[int] = DEFAULT_BUCKET_WIDTHS,
    pad_to_blocks: bool = False,
) -> BucketedMatrix:
    """Threaded two-pass scatter (no sort): numpy computes the O(n_rows)
    bucket/slot assignment, C++ fills the padded slabs deterministically."""
    import ctypes

    from ..native import load_library

    lib = load_library("bucketize")
    lib.pio_bucketize_fill.restype = ctypes.c_int

    nnz = len(rows)
    widths = np.asarray(sorted(bucket_widths), dtype=np.int32)
    max_w = int(widths[-1])
    idx_dtype = _idx_dtype(n_cols)
    counts = np.bincount(rows, minlength=n_rows).astype(np.int32)
    present = np.nonzero(counts)[0].astype(np.int32)  # ascending row ids
    assignment = np.searchsorted(
        widths, np.minimum(counts[present], max_w), side="left"
    )

    bucket_of = np.zeros(n_rows, dtype=np.int32)
    slot_of = np.zeros(n_rows, dtype=np.int32)
    slabs = []  # (sel, counts, b_alloc, idx, val) per width, empties too
    for wi, width in enumerate(widths):
        sel = present[assignment == wi]
        bucket_of[sel] = wi
        slot_of[sel] = np.arange(len(sel), dtype=np.int32)
        cnt = np.minimum(counts[sel], int(width)).astype(np.int32)
        rows_arr, cnt, b_alloc = _alloc_rows(
            sel, cnt, n_rows, width, pad_to_blocks
        )
        slabs.append(
            (
                rows_arr,
                cnt,
                np.zeros(b_alloc * width, dtype=idx_dtype),
                np.zeros(b_alloc * width, dtype=np.float32),
                len(sel),
            )
        )

    i32p, f32p = ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_float)
    voidp = ctypes.c_void_p
    idx_ptrs = (voidp * len(widths))(
        *[s[2].ctypes.data_as(voidp) for s in slabs]
    )
    val_ptrs = (f32p * len(widths))(
        *[s[3].ctypes.data_as(f32p) for s in slabs]
    )
    rc = lib.pio_bucketize_fill(
        rows.ctypes.data_as(i32p),
        cols.ctypes.data_as(i32p),
        vals.ctypes.data_as(f32p),
        ctypes.c_int64(nnz),
        ctypes.c_int64(n_rows),
        bucket_of.ctypes.data_as(i32p),
        slot_of.ctypes.data_as(i32p),
        widths.ctypes.data_as(i32p),
        ctypes.c_int32(len(widths)),
        idx_ptrs,
        val_ptrs,
        ctypes.c_int32(1 if idx_dtype == np.uint16 else 0),
    )
    if rc != 0:
        raise RuntimeError(f"pio_bucketize_fill failed rc={rc}")

    buckets = [
        Bucket(
            rows=rows_arr,
            idx=idx.reshape(len(rows_arr), int(w)),
            val=val.reshape(len(rows_arr), int(w)),
            counts=cnt,
        )
        for w, (rows_arr, cnt, idx, val, n_present) in zip(widths, slabs)
        if n_present
    ]
    return BucketedMatrix(
        n_rows=n_rows, n_cols=n_cols, nnz=int(nnz), buckets=buckets
    )


def _bucketize_numpy(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    n_rows: int,
    n_cols: int,
    bucket_widths: Sequence[int] = DEFAULT_BUCKET_WIDTHS,
    pad_to_blocks: bool = False,
) -> BucketedMatrix:
    """Pure-numpy reference implementation (argsort-based).

    Host-bandwidth-tuned: int32 temporaries throughout (valid while nnz and
    row ids fit in 31 bits), group boundaries from a diff instead of
    ``np.unique``, and validity kept as per-row counts instead of a
    materialized mask.
    """
    nnz = len(rows)
    idx_dtype = _idx_dtype(n_cols)
    order = np.argsort(rows, kind="stable")  # radix for int keys
    rows_s, cols_s, vals_s = rows[order], cols[order], vals[order]
    if nnz:
        boundary = np.nonzero(np.diff(rows_s))[0].astype(np.int64) + 1
        start = np.concatenate([[0], boundary])
        uniq = rows_s[start]
    else:
        start = np.zeros(0, dtype=np.int64)
        uniq = rows_s
    counts = np.diff(np.append(start, nnz))

    buckets: List[Bucket] = []
    widths = sorted(bucket_widths)
    max_w = widths[-1]
    degrees = np.minimum(counts, max_w)
    # assign each row to the smallest width >= degree
    assignment = np.searchsorted(widths, degrees, side="left")

    for wi, width in enumerate(widths):
        sel = np.nonzero(assignment == wi)[0]
        if sel.size == 0:
            continue
        b = sel.size
        c = np.minimum(counts[sel], width).astype(np.int32)
        rows_arr, cnt, b_alloc = _alloc_rows(
            uniq[sel].astype(np.int32), c, n_rows, width, pad_to_blocks
        )
        total = int(c.sum())
        # within-row offsets [0..c0), [0..c1), … concatenated (vectorized)
        cum = np.cumsum(c, dtype=np.int32)
        within = np.arange(total, dtype=np.int32) - np.repeat(cum - c, c)
        src = np.repeat(start[sel].astype(np.int32), c) + within
        dst = np.repeat(
            (np.arange(b, dtype=np.int64) * width).astype(np.int64), c
        ) + within
        idx = np.zeros(b_alloc * width, dtype=idx_dtype)
        val = np.zeros(b_alloc * width, dtype=np.float32)
        idx[dst] = cols_s[src].astype(idx_dtype)
        val[dst] = vals_s[src]
        buckets.append(
            Bucket(
                rows=rows_arr,
                idx=idx.reshape(b_alloc, width),
                val=val.reshape(b_alloc, width),
                counts=cnt,
            )
        )
    return BucketedMatrix(
        n_rows=n_rows, n_cols=n_cols, nnz=int(nnz), buckets=buckets
    )


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """MLlib-compatible knobs (``ALS.train`` signature)."""

    rank: int = 10
    iterations: int = 10
    lambda_: float = 0.01
    implicit_prefs: bool = False
    alpha: float = 1.0  # implicit confidence scale
    seed: int = 0
    #: "auto" (default) resolves at train time: "pallas" on TPU with
    #: rank <= 80 (single-chip or mesh — under a mesh the kernel runs
    #: per-device inside shard_map over the data axis), else "chunked".
    #: "chunked" fuses each block's Cholesky into the chunk map;
    #: "pallas" replaces XLA's batched Cholesky with
    #: the fused transposed-layout kernel
    #: (ops/pallas_kernels.spd_solve_t: 167 ns a 56 x 56 system on a
    #: v5e, 15.5 ns an 8 x 8 one; PERF.md §6, PR 27). Both produce
    #: identical results up to float reassociation.
    solve_mode: str = "auto"
    #: "f32" (default) or "bf16": dtype of the gathered opposite-side
    #: factors feeding the normal-equation einsums (accumulation stays
    #: f32). bf16 halves the gather's HBM bytes and doubles MXU rate at
    #: ~0.4% relative input rounding — the λ·n_u ridge keeps the solves
    #: stable, but quality-gate the result (RMSE) before adopting.
    gather_dtype: str = "f32"

    def resolve_levers(self) -> dict:
        """The CONCRETE lever settings a train run with this config will
        execute — ``solve_mode="auto"`` resolved against the backend.
        One home for the resolution rules, shared by :func:`als_train`
        and the bench/ledger accounting ("record resolved, not
        requested" — docs/performance.md#levers)."""
        solve_mode = self.solve_mode
        if solve_mode == "auto":
            solve_mode = (
                "pallas"
                if (self.rank <= 80 and jax.default_backend() == "tpu")
                else "chunked"
            )
        return {
            "solve_mode": solve_mode,
            "gather_dtype": self.gather_dtype,
            # the ``pallas`` solve builds a bucket as wide as the rank or
            # wider with the fused gather + Gramian kernel
            # (``ops/pallas_kernels.gramian_fused``): factor rows stream
            # HBM→VMEM once and no ``[B, K, R]`` block exists. Reported,
            # not chosen (PERF.md §6, PR 28: the einsum build of those
            # buckets lost its A/B on the chip, 37.8 s a job against 36.0)
            "fused_gather": solve_mode == "pallas",
        }


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------
def _system_explicit_g(g, val, mask, lam, rank):
    """Normal equations from ALREADY-GATHERED masked factors ``g``
    [B, K, R] — the math half of :func:`_system_explicit`, split out so
    the sharded trainer's pipelined off-shard gathers
    (``ops/als_sharded.py``) can issue the gather separately from the
    solve it feeds."""
    # Batched Gramian: MXU matmul [B, R, K] @ [B, K, R]
    a = jnp.einsum("bkr,bks->brs", g, g, preferred_element_type=jnp.float32)
    n_u = mask.astype(jnp.float32).sum(axis=1)  # [B]
    a = a + (lam * n_u)[:, None, None] * jnp.eye(rank, dtype=jnp.float32)
    b = jnp.einsum(
        "bkr,bk->br", g, val.astype(g.dtype),
        preferred_element_type=jnp.float32,
    )
    return a, b


def _system_explicit(y, idx, val, mask, lam, rank):
    """Normal equations for one row block (traceable body).

    y: [N, R] opposite factors (its dtype — f32 or bf16 — sets the gather
    and MXU input precision; accumulation is always f32); idx/val/mask:
    [B, K] with mask matching y's dtype.
    A_u = Gᵀ G + λ n_u I,  b_u = Gᵀ r_u   (G = masked gathered factors)
    """
    with jax.named_scope("als.gather"):
        g = y[idx] * mask[..., None]  # [B, K, R]
    with jax.named_scope("als.gramian"):
        return _system_explicit_g(g, val, mask, lam, rank)


def _system_implicit_g(g, yty, val, mask, lam, alpha, rank):
    """Implicit-feedback normal equations from already-gathered masked
    factors ``g`` [B, K, R] (see :func:`_system_explicit_g`)."""
    maskf = mask.astype(jnp.float32)
    c_minus_1 = (alpha * jnp.abs(val)) * maskf  # [B, K]
    pref = (val > 0).astype(jnp.float32) * maskf  # [B, K]
    a = yty[None] + jnp.einsum(
        "bkr,bk,bks->brs", g, c_minus_1.astype(g.dtype), g,
        preferred_element_type=jnp.float32,
    )
    n_u = maskf.sum(axis=1)
    a = a + (lam * n_u)[:, None, None] * jnp.eye(rank, dtype=jnp.float32)
    b = jnp.einsum(
        "bkr,bk->br", g, ((1.0 + c_minus_1) * pref).astype(g.dtype),
        preferred_element_type=jnp.float32,
    )
    return a, b


def _system_implicit(y, yty, idx, val, mask, lam, alpha, rank):
    """Implicit-feedback normal equations (Hu-Koren-Volinsky, MLlib
    semantics).

    A_u = YᵀY + Σ_observed (c-1) y yᵀ + λ n_u I,  b_u = Σ_observed c·p·y
    with confidence c = 1 + α·|r| and preference p = 1[r > 0] (MLlib's
    ``ALS.scala`` implicit convention: confidence from magnitude, preference
    from sign — a negative rating is high-confidence "not preferred").
    """
    with jax.named_scope("als.gather"):
        g = y[idx] * mask[..., None]  # [B, K, R]
    with jax.named_scope("als.gramian"):
        return _system_implicit_g(g, yty, val, mask, lam, alpha, rank)


def _cho_solve(a, b):
    with jax.named_scope("als.solve"):
        chol = jax.scipy.linalg.cho_factor(a, lower=True)
        return jax.scipy.linalg.cho_solve(chol, b)


@dataclasses.dataclass
class _StagedBucket:
    """Bucket tensors resident on device, pre-chunked along a leading C axis.

    The [B, K] validity mask is NOT transferred: it is a pure function of
    the per-row rating count, so only ``counts`` ([C, B] int32) crosses
    host→device and the mask is rebuilt inside the traced solve — a third
    of the staging bytes."""

    rows: jax.Array  # [C, B] int32 (padded with n_rows → dropped by scatter)
    idx: jax.Array  # [C, B, K] int32, or uint16 when n_cols <= 0xFFFF
    #                 (transfer packing; widened in _solve_side_traced)
    val: jax.Array  # [C, B, K] float32
    counts: jax.Array  # [C, B] int32 — ratings per row (0 on padding)


@dataclasses.dataclass
class StagedMatrix:
    """One side staged on device — transferred once, reused every iteration."""

    n_rows: int
    n_cols: int
    nnz: int
    buckets: List[_StagedBucket]


def _block_rows_for(width: int) -> int:
    block = _BLOCK_ROWS.get(width)
    if block is None:
        # unseen width: bound gather chunk to ~64M floats
        block = max(16, (1 << 26) // max(1, width * 64))
    return min(block, _MAX_BLOCK_ROWS)


def _scope_rung(width: int) -> int:
    """The rung of :data:`_SCOPE_RUNGS` a bucket's device scope is named
    after (its own width past the last rung)."""
    return next((r for r in _SCOPE_RUNGS if r >= width), width)


def stage(
    side: BucketedMatrix, sharding=None, row_multiple: int = 1
) -> StagedMatrix:
    """Move a bucketed matrix to device in chunked layout.

    ``sharding`` (optional ``jax.sharding.Sharding``) shards the block-row
    dimension — the rows being solved — across the mesh data axis;
    ``row_multiple`` rounds the block size up so the sharded dim divides
    evenly over the axis.

    Buckets built with ``bucketize(..., pad_to_blocks=True)`` are already
    chunk-aligned with uint16 indices where applicable: this function then
    only reshapes views and issues the async ``device_put`` — no host
    copies (the copies were ~the whole staging wall-clock on a 1-core
    host).
    """
    staged = []
    for bucket in side.buckets:
        # same right-sizing rule as _alloc_rows: a bucket already padded
        # by bucketize(pad_to_blocks=True) re-chunks to its own size (no
        # re-padding back up to a full block), an unpadded one pads to
        # its pow2 envelope
        n = bucket.rows.shape[0]
        block = _alloc_block(bucket.width, n)
        if row_multiple > 1:
            block = ((block + row_multiple - 1) // row_multiple) * row_multiple
        n_chunks = max(1, (n + block - 1) // block)
        padded = n_chunks * block
        pad = padded - n

        rows, idx, val, counts = (
            bucket.rows, bucket.idx, bucket.val, bucket.counts,
        )
        if pad:
            # rows pad with n_rows sentinel → dropped by the mode="drop"
            # scatter in the solve
            rows = np.pad(rows, (0, pad), constant_values=side.n_rows)
            idx = np.pad(idx, ((0, pad), (0, 0)))
            val = np.pad(val, ((0, pad), (0, 0)))
            counts = np.pad(counts, (0, pad))
        target_dtype = _idx_dtype(side.n_cols)
        if idx.dtype != target_dtype and target_dtype == np.uint16:
            # column ids fit uint16: halves the largest staged tensor's
            # host→device bytes (widened back to int32 inside the traced
            # solve, where the cast fuses for free)
            idx = idx.astype(np.uint16)
        put = (
            (lambda a: jax.device_put(a, sharding))
            if sharding is not None
            else jax.device_put
        )
        staged.append(
            _StagedBucket(
                rows=put(rows.reshape(n_chunks, block)),
                idx=put(idx.reshape(n_chunks, block, bucket.width)),
                val=put(val.reshape(n_chunks, block, bucket.width)),
                counts=put(counts.reshape(n_chunks, block)),
            )
        )
    return StagedMatrix(
        n_rows=side.n_rows, n_cols=side.n_cols, nnz=side.nnz, buckets=staged
    )


def _update_side(
    y: jax.Array,
    side,
    cfg: ALSConfig,
    x_shape: Tuple[int, int],
    yty: Optional[jax.Array],
) -> jax.Array:
    """Solve all rows of one side given the opposite factors ``y`` — a thin
    dispatch over the same traced body the training iteration uses."""
    if isinstance(side, BucketedMatrix):
        side = stage(side)
    return _solve_side_traced(
        y,
        _bucket_tensors(side),
        x_shape[0],
        cfg.rank,
        cfg.implicit_prefs,
        jnp.float32(cfg.lambda_),
        jnp.float32(cfg.alpha),
        yty,
    )


def init_factors(n: int, rank: int, seed: int) -> jax.Array:
    """MLlib-style init: |N(0,1)| / sqrt(rank) keeps initial predictions
    positive and O(1)."""
    key = jax.random.PRNGKey(seed)
    return jnp.abs(jax.random.normal(key, (n, rank), dtype=jnp.float32)) / jnp.sqrt(
        jnp.float32(rank)
    )


@dataclasses.dataclass
class ALSFactors:
    """Trained factor tables (the ``MatrixFactorizationModel`` analogue)."""

    user_factors: jax.Array  # [n_users, rank]
    item_factors: jax.Array  # [n_items, rank]
    rank: int


def _solves_dual(width: int, rank: int, implicit: bool) -> bool:
    """Whether a bucket's rows are solved in the space of their ratings
    (``solve_chunk_dual`` in :func:`_solve_side_traced`): explicit
    feedback and fewer slots than the rank. Read from what the program
    is given; nothing chooses it."""
    return not implicit and width < rank


def _solve_forms(side, rank: int, implicit: bool) -> dict:
    """``{"dual_rows", "primal_rows"}``: the rows of one side (padding
    rows left out) by the form their bucket is solved in."""
    forms = {"dual_rows": 0, "primal_rows": 0}
    for b in side.buckets:
        count = np.count_nonzero if isinstance(b.counts, np.ndarray) else (
            jnp.count_nonzero)
        key = "dual_rows" if _solves_dual(
            b.idx.shape[-1], rank, implicit) else "primal_rows"
        forms[key] += int(count(b.counts))
    return forms


def _bucket_tensors(side: StagedMatrix):
    return tuple((b.rows, b.idx, b.val, b.counts) for b in side.buckets)


def _fused_chunk_solve(
    y_pad, yty_pad, lam, alpha, idx_blk, val_blk, counts_blk,
    *, implicit, rank,
):
    """One chunk's normal equations + SPD solve on the fused Pallas path —
    per-device logic only (no mesh handling): under a mesh the caller
    wraps this whole function in ``shard_map`` over the data axis, so the
    ``[B, K, R]`` gathered intermediate never exists on any device.

    ``yty_pad`` is always an array (zeros in explicit mode) so the
    function is shard_map-able without closures over tracers.
    """
    from .pallas_kernels import _SPD_BLK, gramian_fused, spd_solve_t

    # the kernel gathers inside itself: this path has no ``als.gather``
    with jax.named_scope("als.gramian"):
        k = idx_blk.shape[-1]
        maskf = (
            jnp.arange(k, dtype=jnp.int32)[None, :] < counts_blk[:, None]
        ).astype(jnp.float32)
        if implicit:
            c1 = (alpha * jnp.abs(val_blk)) * maskf
            w2 = c1
            rhs = (1.0 + c1) * ((val_blk > 0).astype(jnp.float32) * maskf)
            yty_arg = yty_pad
        else:
            w2 = maskf
            rhs = val_blk * maskf
            yty_arg = None
        ridge = lam * counts_blk.astype(jnp.float32)
        a, bvec = gramian_fused(y_pad, idx_blk, w2, rhs, ridge, yty_arg)
    with jax.named_scope("als.solve"):
        # [B, R, R] → the solver's lane-batched [R, R, B] layout. This
        # transpose is the one extra HBM round trip the fused path pays
        # (B·R²·4 B — small next to the 2·B·K·R·4 B it removes for K ≳ R;
        # the caller auto-gates on bucket width accordingly).
        a_t = jnp.transpose(a, (1, 2, 0))
        b_t = bvec.T
        bsz = idx_blk.shape[0]
        pad_b = -bsz % _SPD_BLK
        if pad_b:
            a_t = jnp.pad(a_t, ((0, 0), (0, 0), (0, pad_b)))
            b_t = jnp.pad(b_t, ((0, 0), (0, pad_b)))
        x_t = spd_solve_t(a_t, b_t)
        return x_t[:rank, :bsz].T  # [B, rank]


def _solve_side_traced(
    y, buckets, n_rows, rank, implicit, lam, alpha, yty,
    solve_mode="chunked", gather_dtype="f32", mesh=None,
):
    """Unrolled bucket loop inside a traced program (no per-bucket dispatch).

    ``solve_mode``:

    * ``"chunked"`` — each lax.map step builds one block's normal
      equations AND Cholesky-solves it. Minimal live memory, but the
      sequential depth is (chunks × Cholesky's ~R-step loop).
    * ``"pallas"`` — solves with the fused Cholesky kernel
      (``ops/pallas_kernels.spd_solve_t``; the XLA batched Cholesky was
      ~2/3 of the iteration wall-clock on v5e). A bucket as wide as the
      rank or wider builds its normal equations with the fused gather +
      Gramian kernel (``gramian_fused``: the removed ``[B, K, R]`` round
      trip outweighs its ``[B, R, R]`` transpose from there up); a
      narrower one, which only an implicit job has, builds them by
      einsum directly in the solver's transposed ``[R, R, B]`` layout.

    Whatever the mode, an explicit bucket narrower than the rank is
    solved in the dual form (``solve_chunk_dual``: a ``k × k`` system a
    row, by the ``pallas`` mode's kernel at ``n = max(8, k)`` or, under
    ``chunked``, by XLA's Cholesky a block): the rule is
    :func:`_solves_dual`, read from the bucket's shape. Every other
    bucket, and every implicit job, runs the primal program below.

    Under a ``mesh``, the per-chunk SPD systems are embarrassingly
    parallel across solve rows, so the pallas kernel (which does not
    auto-partition under pjit) is wrapped in ``shard_map`` over the
    ``data`` axis: each device Cholesky-solves its local ``[R, R,
    B/n_data]`` block with zero collectives inside the solve. The XLA
    path (``chunked``) partitions automatically and ignores ``mesh``.
    """
    x = jnp.zeros((n_rows, rank), dtype=jnp.float32)
    gdt = jnp.bfloat16 if gather_dtype == "bf16" else jnp.float32
    y_g = y.astype(gdt) if y.dtype != gdt else y

    def expand_mask(idx_blk, counts_blk):
        # validity mask rebuilt on device from per-row counts (free: fuses
        # into the gather/einsum; saves a [B, K] host transfer). Dtype
        # follows the gather so the masked product stays bf16 on the
        # reduced-precision path (0/1 are exact in bf16).
        k = idx_blk.shape[-1]
        return (
            jnp.arange(k, dtype=jnp.int32)[None, :] < counts_blk[:, None]
        ).astype(gdt)

    def system(c):
        mask = expand_mask(c[0], c[2])
        if implicit:
            return _system_implicit(
                y_g, yty, c[0], c[1], mask, lam, alpha, rank
            )
        return _system_explicit(y_g, c[0], c[1], mask, lam, rank)

    if solve_mode == "pallas":
        n_pad = (rank + 7) // 8 * 8
        y_pad = jnp.pad(y_g, ((0, 0), (0, n_pad - rank)))
        yty_pad = (
            jnp.pad(yty, ((0, n_pad - rank), (0, n_pad - rank)))
            if implicit
            else None
        )
        eye_t = jnp.eye(n_pad, dtype=jnp.float32)[:, :, None]

        def spd_solve_lanes(a_t, b_t):
            """``spd_solve_t`` on ``[n, n, B]`` systems, ``B`` padded to
            the kernel's lane block (on every device of a mesh's data
            axis, where each solves its own ``B / n_data`` of them)."""
            from .pallas_kernels import _SPD_BLK, spd_solve_t

            bsz = b_t.shape[-1]
            if mesh is None:
                pad_b = -bsz % _SPD_BLK
                if pad_b:
                    a_t = jnp.pad(a_t, ((0, 0), (0, 0), (0, pad_b)))
                    b_t = jnp.pad(b_t, ((0, 0), (0, pad_b)))
                return spd_solve_t(a_t, b_t)[:, :bsz]
            from jax.sharding import PartitionSpec as P

            from jax import shard_map
            from ..parallel.mesh import DATA_AXIS

            n_data = mesh.shape[DATA_AXIS]
            # each device's local block must itself be a multiple
            # of the kernel's lane block
            pad_b = -bsz % (_SPD_BLK * n_data)
            if pad_b:
                a_t = jnp.pad(a_t, ((0, 0), (0, 0), (0, pad_b)))
                b_t = jnp.pad(b_t, ((0, 0), (0, pad_b)))
            return shard_map(
                spd_solve_t,
                mesh=mesh,
                in_specs=(P(None, None, DATA_AXIS), P(None, DATA_AXIS)),
                out_specs=P(None, DATA_AXIS),
                check_vma=False,  # pallas body; replication by spec
            )(a_t, b_t)[:, :bsz]

        def solve_chunk_pallas(c):
            """An implicit block narrower than the rank (an explicit one
            goes dual, a wider one fused)."""
            idx_blk, val_blk, counts_blk = c
            with jax.named_scope("als.gather"):
                mask = expand_mask(idx_blk, counts_blk)
                g = y_pad[idx_blk] * mask[..., None]  # [B, K, n_pad]
            with jax.named_scope("als.gramian"):
                maskf = mask.astype(jnp.float32)
                c1 = (alpha * jnp.abs(val_blk)) * maskf
                pref = (val_blk > 0).astype(jnp.float32) * maskf
                a_t = yty_pad[:, :, None] + jnp.einsum(
                    "bkr,bk,bks->rsb", g, c1.astype(g.dtype), g,
                    preferred_element_type=jnp.float32,
                )
                n_u = counts_blk.astype(jnp.float32)  # == mask.sum(axis=1)
                a_t = a_t + (lam * n_u)[None, None, :] * eye_t
                b_t = jnp.einsum(
                    "bkr,bk->rb", g, ((1.0 + c1) * pref).astype(g.dtype),
                    preferred_element_type=jnp.float32,
                )
            with jax.named_scope("als.solve"):
                x_t = spd_solve_lanes(a_t, b_t)
                return x_t[:rank].T  # [B, rank]

        def solve_chunk_fused(c):
            idx_blk, val_blk, counts_blk = c
            yty_arg = (
                yty_pad if implicit
                else jnp.zeros((n_pad, n_pad), jnp.float32)
            )
            body = functools.partial(
                _fused_chunk_solve, implicit=implicit, rank=rank
            )
            if mesh is None:
                return body(
                    y_pad, yty_arg, lam, alpha, idx_blk, val_blk, counts_blk
                )
            from jax.sharding import PartitionSpec as P

            from jax import shard_map
            from ..parallel.mesh import DATA_AXIS

            n_data = mesh.shape[DATA_AXIS]
            bsz = idx_blk.shape[0]
            pad_r = -bsz % n_data
            if pad_r:
                idx_blk = jnp.pad(idx_blk, ((0, pad_r), (0, 0)))
                val_blk = jnp.pad(val_blk, ((0, pad_r), (0, 0)))
                counts_blk = jnp.pad(counts_blk, (0, pad_r))
            x_blk = shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    P(), P(), P(), P(), P(DATA_AXIS, None),
                    P(DATA_AXIS, None), P(DATA_AXIS),
                ),
                out_specs=P(DATA_AXIS, None),
                check_vma=False,  # pallas body; replication is by spec
            )(y_pad, yty_arg, lam, alpha, idx_blk, val_blk, counts_blk)
            return x_blk[:bsz]

    def solve_chunk_dual(c):
        """An explicit block narrower than the rank, solved in the space
        of its ratings. For ``G`` ``[k, R]`` (a row's gathered, masked
        factors) and ``c = λ·n_u``,
        ``(GᵀG + cI_R)⁻¹ Gᵀ r = Gᵀ (GGᵀ + cI_k)⁻¹ r``: an identity, so
        for ``k < R`` the row costs a ``k × k`` system and one
        ``[k] × [k, R]`` product, not an ``R × R`` factorisation. Both
        products run in float32 at ``Precision.HIGHEST`` on the same
        rows, or the two sides of the identity would see different
        ``G``. A padded slot (a zero row and column of ``GGᵀ``) takes a
        unit diagonal and a zero rating: its α is exactly 0 for every
        λ, zero included, and a padding row gives x = 0. Implicit ALS
        cannot come here: its base matrix is ``YᵀY + λnI``, not a
        multiple of the identity."""
        idx_blk, val_blk, counts_blk = c
        table = y_pad if solve_mode == "pallas" else y_g
        with jax.named_scope("als.gather"):
            mask = expand_mask(idx_blk, counts_blk)
            g = table[idx_blk] * mask[..., None]  # [B, k, R or n_pad]
        k = idx_blk.shape[-1]
        product = functools.partial(
            jnp.einsum, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        g = g.astype(jnp.float32)
        valid = mask > 0
        ridge = jnp.where(
            valid, (lam * counts_blk.astype(jnp.float32))[:, None], 1.0
        )  # [B, k]
        rhs = jnp.where(valid, val_blk, 0.0)
        eye_k = jnp.eye(k, dtype=jnp.float32)
        if solve_mode == "pallas":
            with jax.named_scope("als.gramian"):
                kern = product("bkr,bjr->kjb", g, g)
                kern = kern + eye_k[:, :, None] * ridge.T[:, None, :]
            with jax.named_scope("als.solve"):
                pad_k = -k % 8  # the kernel solves zero padding to 0
                coef = spd_solve_lanes(
                    jnp.pad(kern, ((0, pad_k), (0, pad_k), (0, 0))),
                    jnp.pad(rhs.T, ((0, pad_k), (0, 0))),
                )[:k]
                x_blk = product("bkr,kb->br", g, coef)
        else:
            with jax.named_scope("als.gramian"):
                kern = product("bkr,bjr->bkj", g, g)
                kern = kern + eye_k[None] * ridge[:, :, None]
            coef = _cho_solve(kern, rhs)
            with jax.named_scope("als.solve"):
                x_blk = product("bkr,bk->br", g, coef)
        return x_blk[:, :rank]

    for rows, idx, val, counts in buckets:
        width = idx.shape[-1]
        # outer: the rung (what the listed per-bucket metrics read, and
        # it must not move when the ladder does); inner: the padded width
        with (
            jax.named_scope(f"als.w{_scope_rung(width)}"),
            jax.named_scope(f"als.k{width}"),
        ):
            if idx.dtype != jnp.int32:
                idx = idx.astype(jnp.int32)  # uint16 transfer packing
            if _solves_dual(width, rank, implicit):
                solved = jax.lax.map(solve_chunk_dual, (idx, val, counts))
            elif solve_mode == "pallas":
                fn = solve_chunk_fused if width >= rank else solve_chunk_pallas
                solved = jax.lax.map(fn, (idx, val, counts))
            else:
                solved = jax.lax.map(lambda c: _cho_solve(*system(c)),
                                     (idx, val, counts))
            with jax.named_scope("als.scatter"):
                x = x.at[rows.reshape(-1)].set(
                    solved.reshape(-1, rank), mode="drop"
                )
    return x


def _solve_side_scoped(
    side, y, buckets, n_rows, rank, implicit, lam, alpha,
    solve_mode, gather_dtype, mesh,
):
    """One side's solve under its device scope, ``als.user_side`` or
    ``als.item_side`` by the side SOLVED (``y`` is the opposite table):
    the implicit path's Gramian of ``y`` (``als.yty``), then every
    bucket. Scopes are names in the program's metadata, read by the
    benchmark's trace reduction; they compile to no operation."""
    with jax.named_scope(f"als.{side}_side"):
        yty = None
        if implicit:
            with jax.named_scope("als.yty"):
                yty = jnp.einsum(
                    "nr,ns->rs", y, y, preferred_element_type=jnp.float32
                )
        return _solve_side_traced(
            y, buckets, n_rows, rank, implicit, lam, alpha, yty,
            solve_mode=solve_mode, gather_dtype=gather_dtype, mesh=mesh,
        )


def _als_iteration_body(
    user_buckets, item_buckets, y, lam, alpha,
    rank, implicit, n_users, n_items, solve_mode="chunked",
    gather_dtype="f32", mesh=None,
):
    """One full ALS iteration (user solve + item solve, all buckets) as a
    single device program — one dispatch per iteration. ``lam``/``alpha``
    are dynamic so hyperparameter sweeps reuse the compilation.

    (A whole-run ``fori_loop`` fusion compiles pathologically on some
    backends; per-iteration fusion keeps dispatch count at
    ``iterations`` while staying cheap to compile.)"""
    x = _solve_side_scoped(
        "user", y, user_buckets, n_users, rank, implicit, lam, alpha,
        solve_mode, gather_dtype, mesh,
    )
    y2 = _solve_side_scoped(
        "item", x, item_buckets, n_items, rank, implicit, lam, alpha,
        solve_mode, gather_dtype, mesh,
    )
    return x, y2


def _als_half_body(
    y, buckets, lam, alpha,
    rank, implicit, n_rows, solve_mode="chunked",
    gather_dtype="f32", mesh=None, side="user",
):
    """One HALF iteration (solve one side from the opposite factors) as its
    own device program. The training loop uses this for the first executed
    iteration only: a program that needs just one side's buckets can start
    the moment that side's host→device transfer lands, so the other side's
    transfer overlaps the first solve instead of gating it — the staging
    overlap of VERDICT r3 item 4. Later iterations keep the fused
    whole-iteration program (one dispatch each)."""
    return _solve_side_scoped(
        side, y, buckets, n_rows, rank, implicit, lam, alpha,
        solve_mode, gather_dtype, mesh,
    )


_HALF_STATICS = (
    "rank", "implicit", "n_rows", "solve_mode",
    "gather_dtype", "mesh", "side",
)

_als_half = functools.partial(
    jax.jit, static_argnames=_HALF_STATICS
)(_als_half_body)


@functools.lru_cache(maxsize=32)
def _als_half_sharded(out_sharding):
    return jax.jit(
        _als_half_body,
        static_argnames=_HALF_STATICS,
        out_shardings=out_sharding,
    )


# ``mesh`` is static: jax.sharding.Mesh is hashable, and the traced program
# embeds per-device pallas blocks via shard_map when it is set.
_als_iteration = functools.partial(
    jax.jit,
    static_argnames=(
        "rank", "implicit", "n_users", "n_items", "solve_mode",
        "gather_dtype", "mesh",
    ),
)(_als_iteration_body)


@functools.lru_cache(maxsize=32)
def _als_iteration_sharded(out_sharding):
    """Jit of the iteration with factor-table output shardings pinned (both
    tables get ``out_sharding``); cached per sharding so sweeps reuse the
    compilation."""
    return jax.jit(
        _als_iteration_body,
        static_argnames=(
            "rank", "implicit", "n_users", "n_items", "solve_mode",
            "gather_dtype", "mesh",
        ),
        out_shardings=(out_sharding, out_sharding),
    )


def als_train(
    by_user,
    by_item,
    cfg: ALSConfig,
    mesh=None,
    factor_sharding: str = "replicated",
    checkpoint=None,
    checkpoint_every: int = 0,
    profile: Optional[dict] = None,
) -> ALSFactors:
    """Alternating solves: items → users → items … for ``cfg.iterations``.

    ``by_user`` holds ratings grouped by user (solving users), ``by_item``
    the transpose (solving items); either :class:`BucketedMatrix` (host) or
    :class:`StagedMatrix` (already on device). Mirrors MLlib's iteration
    order: item factors are initialized and users are solved first. Bucket
    tensors are staged to device once; the full run is one fused device
    program.

    Distributed training: pass a ``jax.sharding.Mesh`` with a ``data`` axis
    (and a ``model`` axis when ``factor_sharding="model"``). Solve rows ride
    the ``data`` axis (the analogue of the reference's RDD partitions);
    factor tables are either replicated (default — XLA all-gathers fresh
    factors each half-iteration over ICI) or row-sharded over ``model``
    (MLlib's ALS block partitioning analogue: gathers become cross-shard
    collectives, for tables too big to replicate). The collective schedule
    is derived by XLA from these annotations, not hand-written.

    ``profile`` (optional dict) receives a perf breakdown: ``stage_s``
    (host→device transfer), ``iteration_s`` (per-iteration wall-clock,
    synchronized), and ``flops_per_iteration`` (padded-shape estimate for
    MFU accounting). Per-iteration sync costs nothing extra: each
    iteration is one device program with a data dependency on the last.
    """
    import time as _time

    if cfg.iterations < 1:
        raise ValueError(f"ALS iterations must be >= 1, got {cfg.iterations}")
    if cfg.solve_mode not in ("auto", "chunked", "pallas"):
        raise ValueError(
            f"solve_mode must be 'auto', 'chunked' or 'pallas', "
            f"got {cfg.solve_mode!r}"
        )
    if cfg.gather_dtype not in ("f32", "bf16"):
        raise ValueError(
            f"gather_dtype must be 'f32' or 'bf16', got {cfg.gather_dtype!r}"
        )
    levers = cfg.resolve_levers()
    solve_mode = levers["solve_mode"]
    # The pallas solve kernel has bounded VMEM scratch (rank padded to a
    # multiple of 8, n²·128·4 bytes) — "auto" selects around that limit;
    # an explicit "pallas" beyond it must fail loudly, not die in
    # Mosaic's allocator. Under a mesh the kernel runs per-device inside
    # shard_map over the data axis (see _solve_side_traced), so
    # distributed training keeps the fused-Cholesky iteration win.
    if cfg.solve_mode == "pallas" and cfg.rank > 80:
        raise ValueError(
            f"solve_mode='pallas' supports rank <= 80 (VMEM scratch "
            f"bound), got rank={cfg.rank}; use 'auto' or 'chunked'"
        )
    fused_gather = levers["fused_gather"]
    rank = cfg.rank

    iteration = _als_iteration
    half = _als_half
    row_sharding = None
    row_multiple = 1
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

        if factor_sharding == "model":
            tbl_spec = NamedSharding(mesh, P(MODEL_AXIS))
        elif factor_sharding == "replicated":
            tbl_spec = NamedSharding(mesh, P())
        else:
            raise ValueError(
                f"factor_sharding must be 'replicated' or 'model', "
                f"got {factor_sharding!r}"
            )
        row_sharding = NamedSharding(mesh, P(None, DATA_AXIS))
        row_multiple = mesh.shape[DATA_AXIS]
        iteration = _als_iteration_sharded(tbl_spec)
        half = _als_half_sharded(tbl_spec)

    t_stage = _time.monotonic()
    # how often the dual form engages: real rows by the form their bucket
    # is solved in (counted before staging, where the counts are host
    # arrays), in ``profile`` and on each program's ``als.enqueue`` span
    forms = {
        "user": _solve_forms(by_user, rank, cfg.implicit_prefs),
        "item": _solve_forms(by_item, rank, cfg.implicit_prefs),
    }
    forms["iteration"] = {
        k: forms["user"][k] + forms["item"][k] for k in forms["user"]
    }
    if isinstance(by_user, BucketedMatrix):
        with span("als.stage", {"side": "user"}):
            by_user = stage(by_user, row_sharding, row_multiple)
    if isinstance(by_item, BucketedMatrix):
        with span("als.stage", {"side": "item"}):
            by_item = stage(by_item, row_sharding, row_multiple)
    if profile is not None:
        profile["stage_s"] = _time.monotonic() - t_stage
        # RESOLVED lever flags — what this run actually executed, not
        # what the config requested (tri-state defaults resolve here);
        # the bench and perf ledger record these (docs/performance.md)
        profile["solve_mode"] = solve_mode
        profile["gather_dtype"] = cfg.gather_dtype
        profile["fused_gather"] = fused_gather
        profile["flops_per_iteration"] = estimate_iteration_flops(
            by_user, by_item, rank, cfg.implicit_prefs
        )
        profile["hbm_bytes_per_iteration"] = estimate_iteration_hbm_bytes(
            by_user, by_item, rank, cfg.gather_dtype,
            fused_gather=fused_gather,
        )
        profile["solve_forms"] = {
            side: [forms[side]["dual_rows"], forms[side]["primal_rows"]]
            for side in ("user", "item")
        }
        profile["bucket_shapes"] = {
            "by_user": [
                [int(np.prod(b.rows.shape)), b.idx.shape[-1]]
                for b in by_user.buckets
            ],
            "by_item": [
                [int(np.prod(b.rows.shape)), b.idx.shape[-1]]
                for b in by_item.buckets
            ],
        }
        profile.setdefault("iteration_s", [])
    with span("als.init_factors"):
        y = init_factors(by_item.n_rows, rank, cfg.seed)  # item factors
        if mesh is not None:
            y = jax.device_put(y, tbl_spec)
        ub, ib = _bucket_tensors(by_user), _bucket_tensors(by_item)
    lam, alpha = jnp.float32(cfg.lambda_), jnp.float32(cfg.alpha)
    x = None

    # step-level resume (SURVEY §5: strictly better than the reference's
    # run-to-completion-or-die ALS). A checkpoint is only resumed when its
    # FULL training configuration matches — rank/shape alone is not identity
    # (two algorithm blocks can share shapes but differ in lambda/seed).
    ck_meta = {
        "rank": rank,
        "lambda": float(cfg.lambda_),
        "alpha": float(cfg.alpha),
        "implicit": bool(cfg.implicit_prefs),
        "seed": int(cfg.seed),
        "nnz": int(by_user.nnz),
    }
    start = 0
    if checkpoint is not None:
        # Scan steps newest-first for the first VALID one: config identity
        # matches, shapes match, and step <= cfg.iterations (a stale
        # higher-step checkpoint from a longer past run must not block
        # resume from an earlier in-range step). An unreadable/corrupt
        # checkpoint is treated as absent, not fatal.
        for step in reversed(checkpoint.all_steps()):
            if step > cfg.iterations:
                continue
            try:
                step, tree, meta = checkpoint.restore(
                    step, like={"x": 0, "y": 0}
                )
            except Exception:
                continue  # torn/corrupt save — keep scanning older steps
            if (
                all(meta.get(k) == v for k, v in ck_meta.items())
                and tuple(tree["y"].shape) == (by_item.n_rows, rank)
                and tuple(tree["x"].shape) == (by_user.n_rows, rank)
            ):
                x = jnp.asarray(tree["x"])
                y = jnp.asarray(tree["y"])
                if mesh is not None:
                    x, y = (
                        jax.device_put(x, tbl_spec),
                        jax.device_put(y, tbl_spec),
                    )
                start = step
                break

    common = dict(
        rank=rank,
        implicit=cfg.implicit_prefs,
        solve_mode=solve_mode,
        gather_dtype=cfg.gather_dtype,
        mesh=mesh if solve_mode == "pallas" else None,
    )
    # jit boundary telemetry (docs/observability.md#profiling): a solve
    # call that compiles is counted (and, past the first, counted as a
    # retrace) — the signal that distinguishes "the solver is slow" from
    # "the solver keeps recompiling"
    from ..obs.profile import default_telemetry

    _telemetry = default_telemetry()
    # the ``als.enqueue`` spans time the host's dispatch of a program,
    # not the program: the host runs ahead of the device, and the
    # program's own time is the device trace's (``jit__als_*`` modules)
    for i in range(start, cfg.iterations):
        t_iter = _time.monotonic()
        if i == start:
            # first executed iteration as two half programs: the user
            # solve needs only the user-side buckets, so it starts as
            # soon as they land while the item-side transfer is still in
            # flight (same math — the fused body is these two calls)
            with span(
                "als.enqueue",
                {"program": "half_user", "i": i, **forms["user"]},
            ):
                x = _telemetry.call(
                    "als_half", half, y, ub, lam, alpha,
                    n_rows=by_user.n_rows, side="user", **common,
                )
            with span(
                "als.enqueue",
                {"program": "half_item", "i": i, **forms["item"]},
            ):
                y = _telemetry.call(
                    "als_half", half, x, ib, lam, alpha,
                    n_rows=by_item.n_rows, side="item", **common,
                )
        else:
            with span(
                "als.enqueue",
                {"program": "iteration", "i": i, **forms["iteration"]},
            ):
                x, y = _telemetry.call(
                    "als_iteration", iteration,
                    ub, ib, y, lam, alpha,
                    n_users=by_user.n_rows,
                    n_items=by_item.n_rows,
                    **common,
                )
        if profile is not None:
            # the fence that ``profile`` has always made, now with a name:
            # without it the host runs ahead and the whole wait is
            # ``train.wait_device`` in ``ALSAlgorithm.train``
            with span("als.wait_device", {"i": i}):
                jax.block_until_ready((x, y))
            profile["iteration_s"].append(_time.monotonic() - t_iter)
        done = i + 1
        if (
            checkpoint is not None
            and checkpoint_every > 0
            and (done % checkpoint_every == 0 or done == cfg.iterations)
        ):
            checkpoint.save(
                done,
                {"x": np.asarray(x), "y": np.asarray(y)},
                {**ck_meta, "iteration": done},
            )
    return ALSFactors(user_factors=x, item_factors=y, rank=rank)


def estimate_iteration_flops(
    by_user: StagedMatrix, by_item: StagedMatrix, rank: int, implicit: bool
) -> float:
    """Padded-shape FLOP estimate for ONE full ALS iteration (both sides) —
    what the device actually executes, for MFU accounting. Per padded row of
    width K: Gramian einsum 2·K·R², rhs einsum 2·K·R, Cholesky ≈ R³/3,
    triangular solves ≈ 2·R²."""
    total = 0.0
    for side in (by_user, by_item):
        for b in side.buckets:
            rows = float(np.prod(b.rows.shape))  # padded rows incl. chunks
            k = float(b.idx.shape[-1])
            total += rows * (
                2.0 * k * rank * rank
                + 2.0 * k * rank
                + rank**3 / 3.0
                + 2.0 * rank * rank
            )
        if implicit:
            total += 2.0 * side.n_cols * rank * rank  # YᵀY
    return total


def estimate_iteration_hbm_bytes(
    by_user: StagedMatrix, by_item: StagedMatrix, rank: int,
    gather_dtype: str = "f32",
    fused_gather: bool = False,
) -> float:
    """Padded-shape HBM-traffic estimate for one full iteration — the ALS
    solve is gather-bound, so bandwidth utilization (not MFU) is the
    honest efficiency number.

    Einsum-built path, per padded row of width K, per side: the factor
    gather reads K·R elements (the dominant term — counted at the gather
    dtype's width, 2 B for bf16), idx/val/counts stream in once, and the
    solved row writes back R floats. Real gathers touch whole (8,128)
    tiles, so treat this as a lower bound on true traffic.

    Fused path (``fused_gather=True``, buckets with K >= rank — narrower
    buckets keep the einsum build, mirroring ``_solve_side_traced``'s
    auto-gate): each rating's factor row moves as ONE lane-aligned
    1×128-lane f32 DMA — 512 B at bench ranks, REGARDLESS of
    ``gather_dtype`` (Mosaic cannot slice a half-width bf16 sublane, so
    the kernel upcasts at entry; ``ops/pallas_kernels.gramian_fused``) —
    plus the [B, R, R] systems written once and re-read through the
    transposed-layout round trip the solver needs. bf16 therefore buys
    bytes only on the einsum path; the fused path's win is removing the
    [B, K, R] intermediate, not narrowing the rows."""
    elt = 2.0 if gather_dtype == "bf16" else 4.0
    lane_pad = float(-(-int(rank) // 128) * 128)  # 1×128-lane DMA floor
    total = 0.0
    for side in (by_user, by_item):
        for b in side.buckets:
            rows = float(np.prod(b.rows.shape))
            k = float(b.idx.shape[-1])
            idx_b = b.idx.dtype.itemsize
            if fused_gather and k >= rank:
                per_row = (
                    k * lane_pad * 4.0  # per-rating aligned row DMAs (f32)
                    + k * (idx_b + 4.0)  # idx + val stream
                    + 4.0  # per-row counts read
                    + 3.0 * rank * rank * 4.0  # A write + transpose trip
                    + 2.0 * rank * 4.0  # rhs vector + solution write
                )
            else:
                per_row = (
                    k * rank * elt  # gathered opposite factors
                    + k * (idx_b + 4.0)  # idx + val stream
                    + 4.0  # per-row counts read
                    + rank * 4.0  # solution write
                )
            total += rows * per_row
    return total


def als_train_coo(
    users: np.ndarray,
    items: np.ndarray,
    ratings: np.ndarray,
    n_users: int,
    n_items: int,
    cfg: ALSConfig,
    mesh=None,
    factor_sharding: str = "replicated",
    checkpoint=None,
    checkpoint_every: int = 0,
) -> ALSFactors:
    """Convenience: COO triplets → bucketized both ways → train."""

    def bucketize_side(side, rows, cols, n_rows, n_cols):
        tags = {"side": side}
        with span("als.bucketize", tags):
            out = bucketize(
                rows, cols, ratings, n_rows, n_cols, pad_to_blocks=True
            )
            # the fill share of the job's padding: ratings held against
            # slots gathered every iteration (rows of padding included)
            tags["ratings"] = sum(int(b.counts.sum()) for b in out.buckets)
            tags["slots"] = sum(b.idx.size for b in out.buckets)
        return out

    by_user = bucketize_side("user", users, items, n_users, n_items)
    by_item = bucketize_side("item", items, users, n_items, n_users)
    return als_train(
        by_user, by_item, cfg, mesh=mesh, factor_sharding=factor_sharding,
        checkpoint=checkpoint, checkpoint_every=checkpoint_every,
    )


@functools.partial(jax.jit, static_argnames=())
def predict_pairs(
    user_factors: jax.Array, item_factors: jax.Array, u: jax.Array, i: jax.Array
) -> jax.Array:
    """r̂ for (user, item) pairs — the RMSE-evaluation path."""
    return jnp.sum(user_factors[u] * item_factors[i], axis=-1)


def rmse(
    factors: ALSFactors, users: np.ndarray, items: np.ndarray, ratings: np.ndarray
) -> float:
    preds = predict_pairs(
        factors.user_factors,
        factors.item_factors,
        jnp.asarray(users, dtype=jnp.int32),
        jnp.asarray(items, dtype=jnp.int32),
    )
    err = preds - jnp.asarray(ratings, dtype=jnp.float32)
    return float(jnp.sqrt(jnp.mean(err * err)))
