"""Streaming infeed tests: chunked columnar scans, incremental indexing,
and the native bucketize fast path.

The reference's analogous surface is the HBase region-split read feeding
executor partitions (``HBPEvents.scala:58-98``); these tests pin the
bounded-memory streaming contract and its equivalence to the one-shot
paths.
"""

import numpy as np
import pytest

from predictionio_tpu.storage.bimap import BiMap
from predictionio_tpu.storage.event import Event, utcnow
from predictionio_tpu.storage.events import EventFilter
from predictionio_tpu.workflow.infeed import (
    StreamingIndexer,
    stream_ratings,
)


def _insert_rates(store, n, app_id=1):
    for j in range(n):
        store.insert(
            Event(
                event="rate",
                entity_type="user",
                entity_id=f"u{j % 7}",
                target_entity_type="item",
                target_entity_id=f"i{j % 5}",
                properties={"rating": float(j % 5) + 1.0},
                event_time=utcnow(),
            ),
            app_id,
        )


# -- chunked columnar scan (runs against sqlite, native, remote) ----------


def test_scan_columnar_iter_chunks_concat_to_full_scan(event_store):
    _insert_rates(event_store, 25)
    full = event_store.scan_columnar(1, EventFilter(event_names=["rate"]))
    chunks = list(
        event_store.scan_columnar_iter(
            1, EventFilter(event_names=["rate"]), chunk_rows=10
        )
    )
    assert [len(c["event"]) for c in chunks] == [10, 10, 5]
    for key in ("event", "entity_id", "target_entity_id", "properties"):
        joined = [v for c in chunks for v in c[key]]
        assert joined == list(full[key])
    joined_t = np.concatenate([c["event_time_ms"] for c in chunks])
    assert np.array_equal(joined_t, full["event_time_ms"])


def test_scan_columnar_iter_respects_limit(event_store):
    _insert_rates(event_store, 20)
    chunks = list(
        event_store.scan_columnar_iter(
            1, EventFilter(event_names=["rate"], limit=12), chunk_rows=5
        )
    )
    assert sum(len(c["event"]) for c in chunks) == 12


def test_scan_columnar_iter_empty(event_store):
    assert list(event_store.scan_columnar_iter(1, EventFilter())) == []


# -- streaming indexer ----------------------------------------------------


def test_streaming_indexer_matches_one_shot_bimap():
    keys = [f"k{j % 13}" for j in range(100)]
    ix = StreamingIndexer()
    parts = [ix.index_chunk(keys[a:a + 9]) for a in range(0, 100, 9)]
    streamed = np.concatenate(parts)
    one_shot = BiMap.string_int(keys)
    assert np.array_equal(streamed, one_shot.map_array(keys))
    assert ix.to_bimap() == one_shot


# -- stream_ratings -------------------------------------------------------


def test_stream_ratings_value_rules_and_skip(event_store):
    _insert_rates(event_store, 12)
    # a 'buy' (fixed value) and a target-less event (skipped)
    event_store.insert(
        Event(event="buy", entity_type="user", entity_id="u0",
              target_entity_type="item", target_entity_id="i9",
              event_time=utcnow()),
        1,
    )
    event_store.insert(
        Event(event="rate", entity_type="user", entity_id="u0",
              properties={"rating": 5.0}, event_time=utcnow()),
        1,
    )
    batch = stream_ratings(
        event_store, 1, {"rate": "rating", "buy": 4.0}, chunk_rows=5
    )
    assert len(batch.users) == 13  # 12 rates + 1 buy; target-less skipped
    # the buy (only interaction with i9) carries the fixed implicit value
    i9 = batch.item_map["i9"]
    assert list(batch.ratings[batch.items == i9]) == [4.0]
    # decoded ids roundtrip
    u0_idx = batch.user_map["u0"]
    assert batch.user_map.inverse[u0_idx] == "u0"


def test_stream_ratings_missing_property_raises(event_store):
    event_store.insert(
        Event(event="rate", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              event_time=utcnow()),
        1,
    )
    with pytest.raises(ValueError, match="rating"):
        stream_ratings(event_store, 1, {"rate": "rating"})


def test_stream_ratings_empty_store(event_store):
    batch = stream_ratings(event_store, 1, {"rate": "rating"})
    assert len(batch.users) == 0 and len(batch.user_map) == 0


# -- hashed big-ID path ---------------------------------------------------


def test_hashed_id_map_basics():
    from predictionio_tpu.storage.bimap import HashedIdMap

    m = HashedIdMap(1 << 16)
    idx = m.map_array([f"user_{j}" for j in range(1000)])
    assert idx.dtype == np.int32
    assert ((idx >= 0) & (idx < (1 << 16))).all()
    # deterministic and salt-sensitive
    again = m.map_array([f"user_{j}" for j in range(1000)])
    assert np.array_equal(idx, again)
    salted = HashedIdMap(1 << 16, salt=7).map_array(
        [f"user_{j}" for j in range(1000)]
    )
    assert not np.array_equal(idx, salted)
    assert m["user_3"] == idx[3]
    with pytest.raises(ValueError, match="power of two"):
        HashedIdMap(1000)
    with pytest.raises(TypeError, match="inverted"):
        m.inverse
    # aliased-id estimate: 1000 ids in 65536 slots ≈ 1-e^-0.0153 ≈ 1.5%
    assert 0.01 < m.expected_collision_fraction(1000) < 0.02
    with pytest.raises(ValueError, match="2\\^31"):
        HashedIdMap(1 << 32)


def test_hashed_batch_matches_pure_python():
    """Native batch fnv1a64 must equal the reference Python implementation
    (and the event log's evlog_fnv1a64 constants)."""
    from predictionio_tpu.storage import bimap as bm

    keys = ["", "a", "user_1", "ü–🎉", "x" * 300]
    native = bm._fnv1a64_batch(keys, salt=5)
    mask = (1 << 64) - 1
    for j, k in enumerate(keys):
        h = 14695981039346656037 ^ 5
        for b in k.encode("utf-8"):
            h = ((h ^ b) * 1099511628211) & mask
        assert native[j] == (h if h else 1)


def test_stream_ratings_hashed_users(event_store):
    from predictionio_tpu.storage.bimap import HashedIdMap

    _insert_rates(event_store, 30)
    exact = stream_ratings(event_store, 1, {"rate": "rating"})
    hashed = stream_ratings(
        event_store, 1, {"rate": "rating"}, hashed_users=1 << 12
    )
    assert isinstance(hashed.user_map, HashedIdMap)
    # same interactions, same item indexing, user indices are the hashes
    assert np.array_equal(hashed.items, exact.items)
    assert np.array_equal(hashed.ratings, exact.ratings)
    u_inv = exact.user_map.inverse
    expect = hashed.user_map.map_array(
        [u_inv[int(u)] for u in exact.users]
    )
    assert np.array_equal(hashed.users, expect)


# -- native ratings scan --------------------------------------------------


@pytest.fixture()
def native_store(tmp_path):
    from predictionio_tpu.native import NativeBuildError

    try:
        from predictionio_tpu.storage.native_events import NativeEventStore

        store = NativeEventStore(str(tmp_path / "ev"))
    except NativeBuildError as exc:
        pytest.skip(f"native event log unavailable: {exc}")
    store.init(1)
    yield store
    store.close()


def test_native_scan_ratings_matches_python_path(native_store):
    _insert_rates(native_store, 40)
    native_store.insert(
        Event(event="buy", entity_type="user", entity_id="u2",
              target_entity_type="item", target_entity_id="i3",
              event_time=utcnow()),
        1,
    )
    rules = {"rate": "rating", "buy": 4.0}
    fast = stream_ratings(native_store, 1, rules)  # native path
    # force the generic chunked path for comparison
    slow_u, slow_i, slow_v = [], [], []

    def grab(u, i, v):
        slow_u.append(u), slow_i.append(i), slow_v.append(v)

    slow = stream_ratings(native_store, 1, rules, chunk_rows=7, on_chunk=grab)
    assert np.array_equal(fast.users, slow.users)
    assert np.array_equal(fast.items, slow.items)
    assert np.array_equal(fast.ratings, slow.ratings)
    assert fast.user_map == slow.user_map
    assert fast.item_map == slow.item_map
    assert len(slow_u) == len(list(slow_u))  # hook saw every chunk


def test_native_scan_ratings_unicode_and_escapes(native_store):
    """The C++ JSON walker must decode escapes exactly as Python json."""
    weird_user = 'u"\\back\nslash\tñ–🎉'
    weird_item = "item/ü\u0007"
    native_store.insert(
        Event(event="rate", entity_type="user", entity_id=weird_user,
              target_entity_type="item", target_entity_id=weird_item,
              properties={"rating": 2.5}, event_time=utcnow()),
        1,
    )
    batch = stream_ratings(native_store, 1, {"rate": "rating"})
    assert list(batch.user_map) == [weird_user]
    assert list(batch.item_map) == [weird_item]
    assert batch.ratings[0] == 2.5


def test_native_scan_ratings_respects_tombstones(native_store):
    _insert_rates(native_store, 5)
    eid = native_store.insert(
        Event(event="rate", entity_type="user", entity_id="uDEAD",
              target_entity_type="item", target_entity_id="iDEAD",
              properties={"rating": 1.0}, event_time=utcnow()),
        1,
    )
    native_store.delete(eid, 1)
    batch = stream_ratings(native_store, 1, {"rate": "rating"})
    assert len(batch.users) == 5
    assert "uDEAD" not in batch.user_map


def test_native_scan_ratings_missing_property_raises(native_store):
    native_store.insert(
        Event(event="rate", entity_type="user", entity_id="u1",
              target_entity_type="item", target_entity_id="i1",
              event_time=utcnow()),
        1,
    )
    with pytest.raises(ValueError, match="missing required property"):
        stream_ratings(native_store, 1, {"rate": "rating"})


# -- native bucketize -----------------------------------------------------


@pytest.mark.parametrize("pad_to_blocks", [False, True])
def test_native_bucketize_matches_numpy(pad_to_blocks):
    from predictionio_tpu.native import NativeBuildError
    from predictionio_tpu.ops.als import _bucketize_native, _bucketize_numpy

    rng = np.random.default_rng(7)
    n_rows, n_cols, nnz = 800, 400, 6_000
    w = 1.0 / np.arange(1, n_rows + 1) ** 1.1
    rows = rng.choice(n_rows, size=nnz, p=w / w.sum()).astype(np.int32)
    cols = rng.integers(0, n_cols, nnz).astype(np.int32)
    vals = rng.normal(size=nnz).astype(np.float32)
    ref = _bucketize_numpy(
        rows, cols, vals, n_rows, n_cols, pad_to_blocks=pad_to_blocks
    )
    # the default ladder, its narrowest rungs and a wide one included
    assert {1, 2, 4, 8, 16, 32, 128, 512} <= {b.width for b in ref.buckets}
    try:
        got = _bucketize_native(
            rows, cols, vals, n_rows, n_cols, pad_to_blocks=pad_to_blocks
        )
    except NativeBuildError as exc:
        pytest.skip(f"native bucketize unavailable: {exc}")
    assert len(ref.buckets) == len(got.buckets)
    for a, b in zip(ref.buckets, got.buckets):
        assert a.idx.dtype == b.idx.dtype and a.idx.shape == b.idx.shape
        assert np.array_equal(a.rows, b.rows)
        assert np.array_equal(a.idx, b.idx)
        assert np.array_equal(a.val, b.val)
        assert np.array_equal(a.counts, b.counts)
        assert np.array_equal(a.mask, b.mask)


def test_native_bucketize_truncation_matches_numpy():
    from predictionio_tpu.native import NativeBuildError
    from predictionio_tpu.ops.als import _bucketize_native, _bucketize_numpy

    rows = np.zeros(100, dtype=np.int32)
    cols = np.arange(100, dtype=np.int32)
    vals = np.arange(100, dtype=np.float32)
    ref = _bucketize_numpy(rows, cols, vals, 1, 100, bucket_widths=(8, 32))
    try:
        got = _bucketize_native(rows, cols, vals, 1, 100, bucket_widths=(8, 32))
    except NativeBuildError as exc:
        pytest.skip(f"native bucketize unavailable: {exc}")
    assert np.array_equal(ref.buckets[0].idx, got.buckets[0].idx)
    assert np.array_equal(ref.buckets[0].val, got.buckets[0].val)
    assert np.array_equal(ref.buckets[0].mask, got.buckets[0].mask)
