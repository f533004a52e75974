"""Remote template gallery tests (Template.scala:56-375 parity).

The environment has no egress, so the gallery contract — ETag conditional
requests, 304 cache hits, offline fallback, zipball extraction — is driven
against a local request-counting HTTP server.
"""

import io
import json
import os
import zipfile
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from predictionio_tpu.tools.gallery import (
    GalleryError,
    fetch_cached,
    get_remote,
    list_remote,
)


def make_zip(files: dict, prefix: str = "") -> bytes:
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as zf:
        for name, content in files.items():
            zf.writestr(prefix + name, content)
    return buf.getvalue()


class _GalleryHandler(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802
        srv = self.server
        srv.hits.setdefault(self.path, []).append(
            self.headers.get("If-None-Match")
        )
        body, etag = srv.routes.get(self.path, (None, None))
        if body is None:
            self.send_response(404)
            self.end_headers()
            return
        if etag and self.headers.get("If-None-Match") == etag:
            self.send_response(304)
            self.end_headers()
            return
        self.send_response(200)
        if etag:
            self.send_header("ETag", etag)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


@pytest.fixture()
def gallery_server(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "base"))
    archive = make_zip(
        {"engine.json": '{"id": "default"}', "engine.py": "# template\n",
         "sub/helper.py": "x = 1\n"},
        prefix="repo-1.0/",  # GitHub-zipball single top folder shape
    )
    index = json.dumps(
        [
            {"name": "gallery-rec", "description": "a remote template",
             "version": "1.0", "archive_url": "/archives/rec.zip"},
        ]
    ).encode()
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _GalleryHandler)
    srv.daemon_threads = True
    srv.routes = {
        "/index.json": (index, '"etag-index-1"'),
        "/archives/rec.zip": (archive, '"etag-zip-1"'),
    }
    srv.hits = {}
    import threading

    threading.Thread(target=srv.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{srv.server_address[1]}/index.json"
    monkeypatch.setenv("PIO_TEMPLATE_GALLERY_URL", url)
    yield srv, url
    srv.shutdown()
    srv.server_close()


def test_list_remote_uses_etag_cache(gallery_server):
    srv, url = gallery_server
    first = list_remote()
    assert first == [
        {"name": "gallery-rec", "description": "a remote template",
         "version": "1.0"}
    ]
    assert srv.hits["/index.json"][0] is None  # no etag on first request
    second = list_remote()
    assert second == first
    # second request was conditional and got a 304 (cache served the body)
    assert srv.hits["/index.json"][1] == '"etag-index-1"'


def test_offline_falls_back_to_cache(gallery_server, monkeypatch):
    srv, url = gallery_server
    assert list_remote() != []
    srv.shutdown()
    srv.server_close()
    assert list_remote() != []  # served from cache
    # a never-fetched URL with no cache raises
    with pytest.raises(GalleryError, match="unreachable"):
        fetch_cached(url.replace("/index.json", "/never.json"))


def test_a_connection_reset_while_reading_falls_back_to_cache(gallery_server, monkeypatch):
    """urllib wraps only the errors of SENDING a request in URLError; a reset
    while the answer is read arrives bare (seen under six test workers, when
    another server took the closed one's port)."""
    import urllib.request

    srv, url = gallery_server
    assert list_remote() != []  # warm the cache

    def reset(*args, **kwargs):
        raise ConnectionResetError(104, "Connection reset by peer")

    monkeypatch.setattr(urllib.request, "urlopen", reset)
    assert list_remote() != []  # served from cache
    with pytest.raises(GalleryError, match="unreachable"):
        fetch_cached(url.replace("/index.json", "/never.json"))


def test_server_error_falls_back_to_cache(gallery_server):
    srv, url = gallery_server
    assert list_remote() != []  # warm the cache
    srv.routes["/index.json"] = (None, None)  # now 404s
    assert list_remote() != []  # served from cache despite HTTP error


def test_get_remote_extracts_and_strips_root(gallery_server, tmp_path):
    srv, url = gallery_server
    target = tmp_path / "proj"
    out = get_remote("gallery-rec", str(target))
    assert out["version"] == "1.0"
    assert (target / "engine.json").read_text() == '{"id": "default"}'
    assert (target / "sub" / "helper.py").read_text() == "x = 1\n"
    with pytest.raises(ValueError, match="not empty"):
        get_remote("gallery-rec", str(target))
    with pytest.raises(KeyError, match="nosuch"):
        get_remote("nosuch", str(tmp_path / "p2"))


def test_get_remote_rejects_zip_slip(gallery_server, tmp_path, monkeypatch):
    srv, url = gallery_server
    evil = make_zip({"../../evil.txt": "pwned"})
    srv.routes["/archives/evil.zip"] = (evil, None)
    srv.routes["/index.json"] = (
        json.dumps(
            [{"name": "evil", "archive_url": "/archives/evil.zip"}]
        ).encode(),
        '"etag-index-2"',
    )
    with pytest.raises(ValueError, match="escapes target"):
        get_remote("evil", str(tmp_path / "p3"))
    assert not (tmp_path / "evil.txt").exists()


def test_console_template_falls_through_to_gallery(gallery_server, tmp_path):
    from predictionio_tpu.tools.console import main

    target = tmp_path / "from-cli"
    rc = main(["template", "get", "gallery-rec", str(target)])
    assert rc == 0
    assert (target / "engine.py").exists()


def test_no_gallery_configured(monkeypatch, tmp_path):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    monkeypatch.delenv("PIO_TEMPLATE_GALLERY_URL", raising=False)
    with pytest.raises(GalleryError, match="No remote gallery"):
        list_remote()
