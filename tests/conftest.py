"""Test fixtures.

Tests run on the CPU backend with 8 virtual devices — the analogue of the
reference's ``local[4]`` Spark test contexts
(``core/src/test/scala/io/prediction/workflow/BaseTest.scala``): multi-device
sharding semantics are exercised without TPU hardware. Env vars must be set
before the first ``import jax`` anywhere in the test process.
"""

import os
import sys

# Tests pin the CPU backend, on the 8-device virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
# No phone-home threads from the train/eval/deploy/build call sites under
# test; the version-check tests drive the mechanism directly.
os.environ["PIO_NO_UPGRADE_CHECK"] = "1"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy compile/AOT/interpret-mode suites excluded from the "
        "tier-1 time budget (`-m 'not slow'`); run them explicitly with "
        "`pytest -m slow`",
    )


@pytest.fixture(params=["sqlite", "native", "remote"])
def event_store(request, tmp_path):
    """Every event-store test runs against the SQLite backend, the native
    (C++) append-only log backend, and the remote (HTTP server-mode)
    backend — the analogue of the reference running its EventsSpec against
    each configured storage source."""
    server = None
    if request.param == "sqlite":
        from predictionio_tpu.storage import SqliteEventStore

        store = SqliteEventStore(":memory:")
    elif request.param == "remote":
        from predictionio_tpu.storage import MetadataStore, SqliteEventStore
        from predictionio_tpu.storage.model_store import SqliteModelStore
        from predictionio_tpu.storage.remote import RemoteEventStore
        from predictionio_tpu.storage.storage_server import StorageServer

        server = StorageServer(
            "127.0.0.1",
            0,
            SqliteEventStore(":memory:"),
            MetadataStore(":memory:"),
            SqliteModelStore(":memory:"),
        )
        server.start_background()
        store = RemoteEventStore(f"http://127.0.0.1:{server.bound_port}")
    else:
        from predictionio_tpu.native import NativeBuildError

        try:
            from predictionio_tpu.storage.native_events import NativeEventStore

            store = NativeEventStore(str(tmp_path / "events_native"))
        except NativeBuildError as exc:  # toolchain-less host only
            pytest.skip(f"native event log unavailable: {exc}")
    store.init(1)
    yield store
    store.close()
    if server is not None:
        server.shutdown()
        server.server_close()


@pytest.fixture()
def metadata_store():
    from predictionio_tpu.storage import MetadataStore

    store = MetadataStore(":memory:")
    yield store
    store.close()
