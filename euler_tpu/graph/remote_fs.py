"""Remote-filesystem graph ingestion: stage fsspec URLs to a local cache.

Role equivalent of the reference's HDFS FileIO
(reference euler/common/hdfs_file_io.cc:79-80 reads graph partitions
straight off HDFS via libhdfs, selected through the scheme-keyed factory at
euler/common/file_io_factory.cc). The TPU-native reshape: the sampling
engine keeps one fast local read path (mmap-friendly, no network stalls in
the hot loop) and remote schemes — ``gs://``, ``s3://``, ``hdfs://``,
``memory://``, anything fsspec resolves — are staged once to a local cache
directory before the engine loads. That is also how TPU VMs are actually
fed (data staged to local SSD), and it is shard-aware: a shard downloads
only its own partitions, mirroring the native selection rule
(eg_engine.cc Engine::Load: partition index p from ``*_<p>.dat``,
kept when ``p % shard_num == shard_idx``).

Staging is idempotent and crash-safe: files land under a tmp name and are
renamed into place; a file already cached with the same size is not
re-fetched. Protocol drivers install separately (e.g. gcsfs for ``gs://``);
a missing driver raises with the package name instead of an opaque import
error.
"""

from __future__ import annotations

import hashlib
import os
import re
import shutil
import threading
from concurrent.futures import ThreadPoolExecutor

_PART_RE = re.compile(r"_(\d+)\.dat$")

#: schemes that are plain local paths even though they carry a "://"
_LOCAL_SCHEMES = ("file", "local")


def is_remote_path(path: str) -> bool:
    """True for fsspec-style URLs that need staging (gs://, s3://, ...)."""
    if "://" not in path:
        return False
    scheme = path.split("://", 1)[0]
    return scheme not in _LOCAL_SCHEMES


def strip_local_scheme(path: str) -> str:
    """file:///data/x -> /data/x; plain paths pass through."""
    for scheme in _LOCAL_SCHEMES:
        prefix = scheme + "://"
        if path.startswith(prefix):
            return path[len(prefix):] or "/"
    return path


def default_cache_dir() -> str:
    return os.environ.get(
        "EULER_TPU_CACHE",
        os.path.join(
            os.path.expanduser("~"), ".cache", "euler_tpu", "staged"
        ),
    )


def _filesystem(url: str):
    try:
        import fsspec
    except ImportError as e:  # pragma: no cover - fsspec is a base dep here
        raise RuntimeError(
            f"loading {url} needs the fsspec package"
        ) from e
    try:
        return fsspec.core.url_to_fs(url)
    except (ImportError, ValueError) as e:
        scheme = url.split("://", 1)[0]
        raise RuntimeError(
            f"no fsspec driver installed for {scheme}:// "
            f"(install e.g. gcsfs for gs://, s3fs for s3://): {e}"
        ) from e


def partition_index(name: str) -> int:
    """Trailing ``_<p>.dat`` partition index; -1 when absent.

    Mirrors the native parser (eg_engine.cc:14-16) so remote staging and
    local loading select identical file sets.
    """
    m = _PART_RE.search(os.path.basename(name))
    return int(m.group(1)) if m else -1


def in_shard(name: str, shard_idx: int, shard_num: int) -> bool:
    """True where file ``name`` is a ``.dat`` partition of this shard —
    the ONE copy of the selection rule, shared by staged and streamed
    ingest and by the local load's byte count, so that none of them can
    pick another file set. It matches the native loader exactly
    (eg_engine.cc Engine::Load): a name without a ``_<p>.dat`` suffix
    belongs to partition 0, so under sharding it goes to shard 0, not
    to no shard."""
    if not name.endswith(".dat"):
        return False
    p = max(partition_index(name), 0)
    return shard_num <= 1 or p % shard_num == shard_idx


def _fetch(fs, remote: str, local: str) -> None:
    # tmp name unique per process AND thread: concurrent stagers (worker
    # processes or threads on one host) must never interleave writes into
    # the same partial file; os.replace publishes only complete files
    tmp = f"{local}.tmp.{os.getpid()}.{threading.get_ident()}"
    try:
        fs.get_file(remote, tmp)
        os.replace(tmp, local)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _shard_partitions(fs, root: str, shard_idx: int, shard_num: int,
                      url: str | None = None):
    """List this shard's ``.dat`` partition entries under ``root``
    (the rule: :func:`in_shard`).

    Returns (partition entries, meta.json entry or None).
    """
    picked = []
    meta = None
    for ent in fs.ls(root, detail=True):
        name = os.path.basename(ent["name"])
        if name == "meta.json":
            meta = ent
        elif in_shard(name, shard_idx, shard_num):
            picked.append(ent)
    if not picked:
        # report the URL the caller actually passed, not the
        # scheme-stripped root — the error must map back to the config
        raise FileNotFoundError(
            f"no .dat partitions for shard {shard_idx}/{shard_num} "
            f"in {url or root}"
        )
    return picked, meta


def stage_directory(
    url: str,
    cache_dir: str | None = None,
    shard_idx: int = 0,
    shard_num: int = 1,
    refresh: bool = False,
) -> str:
    """Download a remote graph directory's ``.dat`` partitions (and
    meta.json when present) for this shard; return the local directory.

    The cache key includes the URL and the shard selection, so different
    shards staged on one host do not collide.
    """
    fs, root = _filesystem(url)
    key = hashlib.sha1(
        f"{url}|{shard_idx}/{shard_num}".encode()
    ).hexdigest()[:16]
    out = os.path.join(cache_dir or default_cache_dir(), key)
    os.makedirs(out, exist_ok=True)

    picked, meta = _shard_partitions(fs, root, shard_idx, shard_num, url)

    want = picked + ([meta] if meta else [])
    keep = {os.path.basename(e["name"]) for e in want}
    # drop cache entries absent from the current remote listing — a
    # repartitioned dataset at the same URL must not mix old and new
    # files when eg_load scans the staged directory
    for name in os.listdir(out):
        if name not in keep and ".tmp." not in name:
            # (.tmp.* files may belong to a concurrent stager mid-fetch)
            os.unlink(os.path.join(out, name))

    def fetch_one(ent):
        name = os.path.basename(ent["name"])
        local = os.path.join(out, name)
        size = ent.get("size")
        if (
            not refresh
            and os.path.exists(local)
            and size is not None
            and os.path.getsize(local) == size
        ):
            return
        _fetch(fs, ent["name"], local)

    # concurrent fetches: object stores serve objects far below host
    # bandwidth; distinct files are safe to fetch in parallel
    with ThreadPoolExecutor(max_workers=min(8, len(want))) as ex:
        list(ex.map(fetch_one, want))
    return out


def read_directory(
    url: str,
    shard_idx: int = 0,
    shard_num: int = 1,
) -> list[tuple[str, bytes]]:
    """Fetch this shard's ``.dat`` partitions straight into memory —
    the STREAMING ingest path (``Graph(..., stream=True)``): bytes go
    fetch → native parse → store with no local staging file, so a host
    needs RAM for the graph but zero local disk (the stage-then-load
    default additionally needs disk ≥ the shard's partition bytes; see
    DEPLOY.md). Same shard-selection rule as stage_directory/eg_load.

    Returns (basename, bytes) pairs; the native merge sorts by name, so
    fetch completion order cannot change the built store.

    RAM budget: the raw partition bytes, their parse-staging copies,
    and the built store are all resident at the peak (inside the one
    ``eg_load_buffers`` call) — plan for roughly raw + store, i.e.
    ~2-3x the store alone. The staged default instead needs local disk
    for the raw bytes and only ``nthreads`` files in memory at once.
    """
    fs, root = _filesystem(url)
    picked, _ = _shard_partitions(fs, root, shard_idx, shard_num, url)
    names = [ent["name"] for ent in picked]
    with ThreadPoolExecutor(max_workers=min(8, len(names))) as ex:
        blobs = list(ex.map(fs.cat_file, names))
    return [(os.path.basename(p), b) for p, b in zip(names, blobs)]


def _reject_duplicates(urls: list[str]) -> None:
    """Duplicate URLs in an explicit file list must fail loudly here:
    they would reach the native name-sorted merge as equal keys, where
    std::sort leaves their relative order unspecified — the built store
    would differ run to run with no hint why."""
    seen: set[str] = set()
    dups = sorted({u for u in urls if u in seen or seen.add(u)})
    if dups:
        raise ValueError(
            f"duplicate file URL(s) in files=: {dups} (the native merge "
            "sorts by name, so every name must be unique for a "
            "deterministic store)"
        )


def read_files(urls: list[str]) -> list[tuple[str, bytes]]:
    """Streamed counterpart of stage_files: fetch each file's bytes —
    remote via fsspec, local straight off disk — with no staging copy.
    The full URL/path is the returned name (basenames in an explicit
    file list can collide, and the native merge sorts by name, so names
    must be unique for the order to be deterministic; duplicates raise).
    """
    _reject_duplicates(urls)

    def fetch_one(url: str) -> tuple[str, bytes]:
        if is_remote_path(url):
            fs, path = _filesystem(url)
            try:
                return url, fs.cat_file(path)
            except FileNotFoundError:
                raise FileNotFoundError(f"no such remote file: {url}")
        local = strip_local_scheme(url)
        with open(local, "rb") as f:
            return url, f.read()

    if not urls:
        return []
    # concurrent like stage/read_directory: object stores serve objects
    # far below host bandwidth
    with ThreadPoolExecutor(max_workers=min(8, len(urls))) as ex:
        return list(ex.map(fetch_one, urls))


def stage_files(
    urls: list[str],
    cache_dir: str | None = None,
    refresh: bool = False,
) -> list[str]:
    """Stage an explicit file list; local paths pass through untouched.
    Duplicate URLs raise, for the same determinism reason as read_files."""
    _reject_duplicates(urls)
    out = []
    for url in urls:
        if not is_remote_path(url):
            out.append(strip_local_scheme(url))
            continue
        fs, path = _filesystem(url)
        key = hashlib.sha1(url.encode()).hexdigest()[:16]
        d = os.path.join(cache_dir or default_cache_dir(), key)
        os.makedirs(d, exist_ok=True)
        local = os.path.join(d, os.path.basename(path))
        try:
            size = fs.info(path).get("size")
        except FileNotFoundError:
            raise FileNotFoundError(f"no such remote file: {url}")
        fresh = (
            not refresh
            and os.path.exists(local)
            and size is not None
            and os.path.getsize(local) == size
        )
        if not fresh:
            _fetch(fs, path, local)
        out.append(local)
    return out


def clear_cache(cache_dir: str | None = None) -> None:
    d = cache_dir or default_cache_dir()
    if os.path.isdir(d):
        shutil.rmtree(d)
