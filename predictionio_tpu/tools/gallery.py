"""Remote template gallery: fetch engine templates from a network index.

Rebuild of the reference's GitHub-backed gallery
(``tools/src/main/scala/io/prediction/tools/console/Template.scala:56-375``):
there, ``pio template list``/``get`` hit the GitHub API (repo tags →
zipball) with an **ETag cache** so repeated calls cost one conditional
request, fall back to the cached copy when offline, and honor an HTTP
proxy. The rebuild keeps the same contract against a self-describable
index:

* ``PIO_TEMPLATE_GALLERY_URL`` points at an index JSON:
  ``[{"name", "description", "archive_url", "version"}, ...]``
* every GET sends ``If-None-Match`` with the cached ETag; 304 → cache hit
  (``Template.scala:62-92``'s ``readMetadataFromCache``/ETag header dance)
* network failure falls back to the cache when present
  (``Template.scala:106-113``)
* proxies: urllib honors ``http_proxy``/``https_proxy`` env vars, the same
  knobs the reference reads (``Template.scala:115-135``)
* ``get`` downloads the template's zip archive and extracts it into the
  target directory (the zipball unpack, ``Template.scala:287-340``; the
  Scala package-rename step has no Python analogue and is dropped)

Cache layout: ``$PIO_FS_BASEDIR/template_cache/<sha1(url)>.{body,etag}``.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import urllib.error
import urllib.request
import zipfile
from typing import List, Optional, Tuple

GALLERY_URL_ENV = "PIO_TEMPLATE_GALLERY_URL"


class GalleryError(Exception):
    """Gallery unreachable and no cached copy exists."""


def gallery_url() -> Optional[str]:
    return os.environ.get(GALLERY_URL_ENV) or None


def _cache_dir() -> str:
    from ..storage.registry import base_dir

    d = os.path.join(base_dir(), "template_cache")
    os.makedirs(d, exist_ok=True)
    return d


def _cache_paths(url: str) -> Tuple[str, str]:
    key = hashlib.sha1(url.encode("utf-8")).hexdigest()
    root = _cache_dir()
    return os.path.join(root, f"{key}.body"), os.path.join(root, f"{key}.etag")


def fetch_cached(url: str, timeout: float = 30.0) -> bytes:
    """GET with ETag conditional-request caching and offline fallback."""
    body_path, etag_path = _cache_paths(url)
    headers = {}
    if os.path.exists(body_path) and os.path.exists(etag_path):
        with open(etag_path, "r", encoding="utf-8") as fh:
            etag = fh.read().strip()
        if etag:
            headers["If-None-Match"] = etag
    req = urllib.request.Request(url, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            body = resp.read()
            etag = resp.headers.get("ETag", "")
            with open(body_path, "wb") as fh:
                fh.write(body)
            with open(etag_path, "w", encoding="utf-8") as fh:
                fh.write(etag)
            return body
    except urllib.error.HTTPError as exc:
        if os.path.exists(body_path):
            # 304: the conditional request validated the cache. Any other
            # HTTP error (5xx from the gallery or a proxy): degrade to the
            # cached copy, same as being unreachable (Template.scala:106-113).
            with open(body_path, "rb") as fh:
                return fh.read()
        raise GalleryError(f"GET {url} → HTTP {exc.code}") from exc
    except (urllib.error.URLError, ConnectionError) as exc:
        # offline, or the connection reset while the answer was read (urllib
        # wraps only the errors of sending the request): serve the cache when
        # we have one (Template.scala:106-113)
        if os.path.exists(body_path):
            with open(body_path, "rb") as fh:
                return fh.read()
        raise GalleryError(f"GET {url} unreachable: {getattr(exc, 'reason', exc)}") from exc


def list_remote(url: Optional[str] = None) -> List[dict]:
    """``pio template list`` against the remote index."""
    url = url or gallery_url()
    if not url:
        raise GalleryError(
            f"No remote gallery configured (set {GALLERY_URL_ENV})"
        )
    entries = json.loads(fetch_cached(url))
    return [
        {
            "name": e["name"],
            "description": e.get("description", ""),
            "version": e.get("version", ""),
        }
        for e in entries
    ]


def get_remote(name: str, directory: str, url: Optional[str] = None) -> dict:
    """``pio template get`` from the remote gallery: download the archive
    (ETag-cached) and extract it into ``directory``."""
    url = url or gallery_url()
    if not url:
        raise GalleryError(
            f"No remote gallery configured (set {GALLERY_URL_ENV})"
        )
    entries = json.loads(fetch_cached(url))
    entry = next((e for e in entries if e["name"] == name), None)
    if entry is None:
        raise KeyError(
            f"Template {name!r} not in gallery; available: "
            f"{sorted(e['name'] for e in entries)}"
        )
    # validate the target before paying for the download; realpath so the
    # zip-slip containment check below agrees with symlinked targets
    directory = os.path.realpath(directory)
    if os.path.exists(directory) and os.listdir(directory):
        raise ValueError(f"Target directory {directory} is not empty")

    archive_url = entry["archive_url"]
    if not archive_url.startswith(("http://", "https://")):
        # relative to the index (the common same-host layout)
        archive_url = urllib.request.urljoin(url, archive_url)
    blob = fetch_cached(archive_url)
    os.makedirs(directory, exist_ok=True)
    with zipfile.ZipFile(io.BytesIO(blob)) as zf:
        names = zf.namelist()
        # strip a single top-level folder (GitHub-zipball shape) when present
        roots = {n.split("/", 1)[0] for n in names if n.strip("/")}
        strip = (
            f"{next(iter(roots))}/"
            if len(roots) == 1 and all("/" in n for n in names if n.strip("/"))
            else ""
        )
        for member in names:
            rel = member[len(strip):] if strip else member
            if not rel or rel.endswith("/"):
                continue
            # zip-slip guard: resolved path must stay inside the target
            dest = os.path.realpath(os.path.join(directory, rel))
            if dest != directory and not dest.startswith(directory + os.sep):
                raise ValueError(f"Archive member escapes target dir: {member}")
            os.makedirs(os.path.dirname(dest), exist_ok=True)
            with zf.open(member) as src, open(dest, "wb") as out:
                out.write(src.read())
    return {
        "template": name,
        "directory": directory,
        "version": entry.get("version", ""),
        "source": archive_url,
    }
