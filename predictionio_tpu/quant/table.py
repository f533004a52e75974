"""Quantized factor tables: int8 rows + per-row f32 scales, exactness-gated.

The serving memory layer of the bandwidth arc (docs/quantization.md): a
rank-R f32 factor row costs 4R bytes; its int8 twin costs R code bytes
plus one f32 scale — 3.7x smaller at the bench's rank 50, so one host
holds multiples of the catalog. Symmetric absmax quantization per row:

    scale_i = max_j |row_ij| / 127        codes_ij = round(row_ij / scale_i)
    dequant_ij = codes_ij * scale_i

Per-row scales factor OUT of the serving dot product, so the quantized
score kernel reads only the int8 codes (the bandwidth win) and applies
scales to the score matrix — the dequantized f32 table never
materializes (:func:`top_k_quantized`).

Quantization is lossy, so serving from codes is allowed only through
the exactness gate — the bf16 RMSE gate discipline (PR 12) extended
from a scalar drift bound to id identity: the quantized top-k ids must
match the f32 top-k on a probe set, and a mismatch is a loud refusal
(:class:`QuantGateError` + counted metric), never a silent quality
slide. ``fp8`` tables sit behind a capability probe and fall back to
int8 LOUDLY off accelerator.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import threading
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ragged import ragged_gather

#: symmetric int8 grid: codes live in [-127, 127] (-128 unused so the
#: grid negates onto itself and |dequant| <= row absmax exactly)
INT8_QMAX = 127.0

#: fp8 e4m3 finite max — the fp8 grid normalizes row absmax onto it
FP8_QMAX = 448.0


class QuantGateError(ValueError):
    """The exactness gate refused a quantized serving table."""


def resolve_quantized_serving(
    explicit: Optional[bool], env: Optional[str] = None
) -> bool:
    """Resolve the ``quantized_serving`` tri-state lever (PR-12
    discipline): an explicit True/False wins, ``None`` resolves from
    ``PIO_SERVE_QUANT`` ("1"/"0"; what ``pio deploy`` environments
    set), else OFF. An unparseable env value fails loudly — a silently
    ignored flag would corrupt the hardware A/B."""
    if explicit is not None:
        return bool(explicit)
    if env is None:
        env = os.environ.get("PIO_SERVE_QUANT")
    if env is None or env == "":
        return False
    if env not in ("0", "1"):
        raise ValueError(
            f"PIO_SERVE_QUANT must be '0' or '1', got {env!r}"
        )
    return env == "1"


# gate outcome counters ("mismatch = loud refusal + counted metric"):
# module-level so every server surface exports the same truth — the
# query server publishes them as pio_quant_gate_{runs,refusals}_total
# via gauge callbacks (workflow/serving.py) and /status.json echoes them
_GATE_LOCK = threading.Lock()
_GATE_COUNTS = {"runs": 0, "refusals": 0}


def gate_counts() -> dict:
    """Snapshot of exactness-gate outcomes for this process."""
    with _GATE_LOCK:
        return dict(_GATE_COUNTS)


def _gate_tally(key: str) -> None:
    with _GATE_LOCK:
        _GATE_COUNTS[key] += 1


@dataclasses.dataclass(frozen=True)
class QuantizedTable:
    """A factor table quantized for serving: codes + per-row scales.

    Plain numpy arrays (like :class:`models.recommendation.ALSModel`) so
    the table blob-persists and ships across processes; kernels lift to
    device on use.
    """

    codes: np.ndarray  # [N, R] int8 (or fp8-encoded) codes
    scales: np.ndarray  # [N] f32 per-row scales; dequant = codes * scale
    dtype: str = "int8"  # "int8" | "fp8"
    #: set when a requested dtype fell back (capability probe), e.g.
    #: "fp8->int8: no fp8 matmul on cpu" — surfaced at /status.json so
    #: the fallback is visible, never silent
    fallback: Optional[str] = None

    @property
    def n_rows(self) -> int:
        return int(self.codes.shape[0])

    @property
    def rank(self) -> int:
        return int(self.codes.shape[1])

    @property
    def table_bytes(self) -> int:
        """Actual serving footprint: codes + scales."""
        return int(self.codes.nbytes + self.scales.nbytes)

    @property
    def f32_bytes(self) -> int:
        """The f32 twin's footprint (the compression baseline)."""
        return int(self.n_rows * self.rank * 4)

    @property
    def compression_ratio(self) -> float:
        return self.f32_bytes / max(self.table_bytes, 1)

    def status(self) -> dict:
        """The /status.json + profile shape: dtype, bytes, compression."""
        out = {
            "dtype": self.dtype,
            "tableBytes": self.table_bytes,
            "f32Bytes": self.f32_bytes,
            "compression": round(self.compression_ratio, 2),
        }
        if self.fallback:
            out["fallback"] = self.fallback
        return out


def fp8_supported() -> bool:
    """Capability probe for fp8 serving tables.

    fp8 codes only pay off where the matmul units consume them (TPU
    v5+/recent GPUs); on CPU XLA widens element-wise, which is slower
    than both int8 and f32 — a trap, not a lever. The probe keys on the
    active backend, so the same config deploys everywhere and the
    fallback (to int8) is taken — loudly — exactly where fp8 would lose.
    """
    if not hasattr(jnp, "float8_e4m3fn"):  # pragma: no cover - old jaxlib
        return False
    return jax.default_backend() in ("tpu", "gpu")


def _normalized_rows(
    table: np.ndarray, qmax: float
) -> Tuple[np.ndarray, np.ndarray]:
    """Rows scaled onto the [-qmax, qmax] grid + the per-row scales.

    Zero rows get scale 0.0 (their codes are 0; dequant reproduces the
    zero row exactly instead of dividing by zero).
    """
    absmax = np.abs(table).max(axis=1)
    scales = (absmax / qmax).astype(np.float32)
    safe = np.where(scales > 0.0, scales, 1.0).astype(np.float32)
    return table / safe[:, None], scales


def quantize_table(table, dtype: str = "int8") -> QuantizedTable:
    """Quantize an f32 factor table (symmetric absmax, per-row scales).

    The ungated constructor — bench twins and tests use it directly;
    the serve path goes through :func:`quantize_serving_table`, which
    is this plus the exactness gate. ``dtype="fp8"`` requires
    :func:`fp8_supported`; off accelerator it falls back to int8 with a
    warning and a ``fallback`` marker on the table (loud, recorded,
    never silent).
    """
    if dtype not in ("int8", "fp8"):
        raise ValueError(
            f"quantize_table dtype must be 'int8' or 'fp8', got {dtype!r}"
        )
    fallback = None
    if dtype == "fp8" and not fp8_supported():
        fallback = (
            f"fp8->int8: no fp8 matmul on {jax.default_backend()} "
            "(docs/quantization.md#fp8)"
        )
        warnings.warn(fallback, stacklevel=2)
        dtype = "int8"
    table = np.asarray(table, dtype=np.float32)
    if table.ndim != 2:
        raise ValueError(f"factor table must be 2-D, got shape {table.shape}")
    if dtype == "int8":
        normalized, scales = _normalized_rows(table, INT8_QMAX)
        codes = np.rint(np.clip(normalized, -INT8_QMAX, INT8_QMAX)).astype(
            np.int8
        )
    else:
        normalized, scales = _normalized_rows(table, FP8_QMAX)
        codes = np.asarray(jnp.asarray(normalized).astype(jnp.float8_e4m3fn))
    return QuantizedTable(
        codes=codes, scales=scales, dtype=dtype, fallback=fallback
    )


def dequantize_rows(qtable: QuantizedTable, ids):
    """Fused dequant-on-gather: f32 rows for ``ids``, each unique row
    dequantized once.

    The one kernel home for reconstructing f32 factors from a quantized
    table — the ragged idiom applied to dequantization: unique the ids,
    gather + scale each referenced row once, replay duplicates through
    the inverse map. Exact dequantization (codes * scale), so
    ``dequantize_rows(quantize_table(t), ids)`` is bit-identical to
    dequantizing the whole table and indexing it.
    """
    idx = jnp.asarray(ids, jnp.int32)
    flat = idx.reshape(-1)
    rank = int(qtable.codes.shape[1])
    if flat.shape[0] == 0:
        return jnp.zeros(idx.shape + (rank,), jnp.float32)
    uniq, inverse = jnp.unique(
        flat, size=flat.shape[0], return_inverse=True, fill_value=0
    )
    rows = jnp.asarray(qtable.codes)[uniq].astype(jnp.float32)
    rows = rows * jnp.asarray(qtable.scales)[uniq][:, None]
    return rows[inverse.reshape(-1)].reshape(idx.shape + (rank,))


def estimate_table_bytes(n_rows: int, rank: int, dtype: str = "f32") -> float:
    """Serving footprint model for one factor table — the quant member
    of the ``estimate_*_hbm_bytes`` family (honest roofline accounting;
    not yet compared with a chip's own counters).

    f32: 4 bytes/element. int8/fp8: 1 byte/element + one f32 scale per
    row. Pinned against actual ``QuantizedTable.table_bytes`` in tests.
    """
    if dtype == "f32":
        return float(n_rows) * rank * 4.0
    if dtype in ("int8", "fp8"):
        return float(n_rows) * (rank * 1.0 + 4.0)
    raise ValueError(f"unknown table dtype {dtype!r}")


def estimate_quant_topk_hbm_bytes(
    b: int, n_items: int, rank: int, k: int, dtype: str = "int8"
) -> float:
    """HBM-traffic model for one quantized top-k dispatch — the
    companion of ``ops.scoring.estimate_topk_hbm_bytes``'s dense leg
    with the item-table read priced at the quantized width (the whole
    point: the score matrix terms are unchanged, the table read
    shrinks ~4x)."""
    queries = float(b) * rank * 4.0
    items = estimate_table_bytes(n_items, rank, dtype)
    results = float(b) * k * 8.0
    score_matrix = float(b) * n_items * 4.0
    return queries + items + 2.0 * score_matrix + results


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_quant(q, codes, scales, k):
    scores = (
        jnp.einsum(
            "br,ir->bi",
            q,
            codes.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        * scales[None, :]
    )
    n_items = codes.shape[0]
    k_eff = min(k, n_items)
    s, i = jax.lax.top_k(scores, k_eff)
    # sentinel contract parity with ops.scoring: -inf slots carry -1
    i = jnp.where(jnp.isneginf(s), -1, i.astype(jnp.int32))
    if k_eff < k:
        neg_inf = float("-inf")
        s = jnp.pad(s, ((0, 0), (0, k - k_eff)), constant_values=neg_inf)
        i = jnp.pad(i, ((0, 0), (0, k - k_eff)), constant_values=-1)
    return s, i


def top_k_quantized(user_factors, qtable: QuantizedTable, user_idx, k: int):
    """Fused quantized score+select: top-k items scored from int8 codes.

    ``scores = (q @ codes^T) * scale`` — per-row scales factor out of
    the dot product, so the kernel reads the narrow codes (the
    bandwidth win) and applies scales to the [B, k-candidate] score
    matrix; the dequantized f32 table never materializes. The user-row
    gather rides :func:`ragged_gather` (duplicate users in a batch cost
    one row read). Same (scores [B, k], ids [B, k]) result contract and
    (-inf, -1) sentinels as ``ops.scoring.top_k_for_users_fused``.
    """
    q = ragged_gather(user_factors, jnp.asarray(user_idx, jnp.int32))
    return _topk_quant(
        q, jnp.asarray(qtable.codes), jnp.asarray(qtable.scales), int(k)
    )


def default_probe_idx(n_rows: int, probes: int = 64) -> np.ndarray:
    """The held-out probe set: evenly spaced user rows, catalog-spanning
    and deterministic (the gate must refuse reproducibly, not
    probabilistically)."""
    if n_rows <= 0:
        return np.zeros(0, dtype=np.int32)
    return np.unique(
        np.linspace(0, n_rows - 1, num=min(int(probes), n_rows))
        .round()
        .astype(np.int32)
    )


def topk_match_gate(
    user_factors, item_factors, qtable: QuantizedTable, probe_idx, k: int
) -> float:
    """Fraction of probe rows whose quantized top-k id set equals the
    f32 top-k id set.

    Id-SET identity, not rank order: quantization noise may reorder
    near-ties *within* the retrieved set, but membership is the serving
    contract (the fleet merge and fold-in equivalence both key on which
    items are returned). 1.0 means every probe user would receive
    exactly the same items quantized as f32.
    """
    from ..ops.scoring import top_k_for_users_fused

    idx = np.asarray(probe_idx, dtype=np.int32)
    if idx.size == 0:
        return 1.0
    k = int(min(k, np.asarray(item_factors).shape[0]))
    _, ref_ids = top_k_for_users_fused(
        user_factors, item_factors, idx, k=k, mode="never"
    )
    _, quant_ids = top_k_quantized(user_factors, qtable, idx, k=k)
    ref = np.sort(np.asarray(ref_ids), axis=1)
    got = np.sort(np.asarray(quant_ids), axis=1)
    return float(np.mean(np.all(ref == got, axis=1)))


def quantize_serving_table(
    item_factors,
    user_factors,
    *,
    dtype: str = "int8",
    probe_idx=None,
    k: int = 10,
    min_match: float = 1.0,
) -> Tuple[QuantizedTable, dict]:
    """Quantize an item table FOR SERVING: quantize + exactness gate.

    The only constructor the serve path may use. Runs at model attach
    (train / fold-in / first serve of a loaded model) and proves the
    quantized top-k ids match the f32 top-k on the probe set before any
    quantized answer is produced. Returns ``(table, gate_status)``;
    raises :class:`QuantGateError` on refusal — loud and counted
    (``pio_quant_gate_refusals_total``), never a silent quality slide.
    """
    item_factors = np.asarray(item_factors, dtype=np.float32)
    if dtype == "int8":
        # int8 encode inlined: the narrowing cast and the gate that
        # licenses it share one scope — the adjacency the lint rule
        # spmd-unguarded-downcast pins (mutation-tested; do not hoist
        # the cast out of this function)
        normalized, scales = _normalized_rows(item_factors, INT8_QMAX)
        codes = np.rint(np.clip(normalized, -INT8_QMAX, INT8_QMAX)).astype(
            np.int8
        )
        qtable = QuantizedTable(codes=codes, scales=scales, dtype="int8")
    else:
        qtable = quantize_table(item_factors, dtype=dtype)
    if probe_idx is None:
        probe_idx = default_probe_idx(np.asarray(user_factors).shape[0])
    probe_idx = np.asarray(probe_idx, dtype=np.int32)
    _gate_tally("runs")
    match_rate = topk_match_gate(
        user_factors, item_factors, qtable, probe_idx, k
    )
    gate_status = dict(qtable.status())
    gate_status.update(
        matchRate=round(match_rate, 4),
        probes=int(probe_idx.size),
        k=int(min(k, item_factors.shape[0])),
    )
    if match_rate < min_match:
        _gate_tally("refusals")
        raise QuantGateError(
            f"quantized serving REFUSED: top-k match rate "
            f"{match_rate:.4f} < required {min_match} (dtype="
            f"{qtable.dtype}, k={gate_status['k']}, probes="
            f"{gate_status['probes']}) — the model serves f32 or not at "
            "all; see docs/quantization.md#gate"
        )
    return qtable, gate_status
