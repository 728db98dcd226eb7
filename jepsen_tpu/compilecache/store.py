"""The persistent AOT entry store: ``<cache dir>/*.aotx``.

One file per executable, named by its content fingerprint (program
HLO digest x shape class x backend/platform string).  File format::

    JTCC1\\n  <sha256-hex of payload>\\n  <payload>

where payload is a pickle of ``{"meta": {...}, "payload":
serialize_executable.serialize(...) tuple}``.  The digest line makes
every read self-verifying: a truncated or bit-flipped entry fails the
check, is deleted, and the caller falls through to a fresh compile
that re-serializes it — the chaos round's "never wedge or corrupt"
contract.

Writes are atomic (tmp + ``os.replace``), so a ``kill -9`` mid-put
leaves either no entry or a whole one; concurrent writers of the same
fingerprint converge on identical content.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("jepsen.compilecache")

__all__ = ["SUFFIX", "entry_path", "put", "get", "delete", "entries",
           "total_bytes", "pack_entry", "unpack_entry", "file_digest"]

MAGIC = b"JTCC1\n"
SUFFIX = ".aotx"


def entry_path(cache_dir: str, fingerprint: str) -> str:
    return os.path.join(cache_dir, fingerprint + SUFFIX)


def pack_entry(meta: Dict[str, Any], payload: Any) -> bytes:
    """Serialize one entry to its on-disk bytes (magic + digest +
    pickle)."""
    body = pickle.dumps({"meta": meta, "payload": payload},
                        protocol=pickle.HIGHEST_PROTOCOL)
    digest = hashlib.sha256(body).hexdigest().encode()
    return MAGIC + digest + b"\n" + body


def unpack_entry(blob: bytes) -> Optional[Dict[str, Any]]:
    """Parse + verify one entry's bytes; None on any corruption (bad
    magic, digest mismatch, unpicklable body)."""
    if not blob.startswith(MAGIC):
        return None
    rest = blob[len(MAGIC):]
    nl = rest.find(b"\n")
    if nl != 64:  # sha256 hex
        return None
    digest, body = rest[:nl].decode("ascii", "replace"), rest[nl + 1:]
    if hashlib.sha256(body).hexdigest() != digest:
        return None
    try:
        doc = pickle.loads(body)
    except Exception:  # noqa: BLE001 — corrupt pickle = corrupt entry
        return None
    return doc if isinstance(doc, dict) and "payload" in doc else None


def put(cache_dir: str, fingerprint: str, meta: Dict[str, Any],
        payload: Any) -> int:
    """Atomically write one entry; returns bytes written."""
    os.makedirs(cache_dir, exist_ok=True)
    blob = pack_entry(meta, payload)
    path = entry_path(cache_dir, fingerprint)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return len(blob)


def get(cache_dir: str, fingerprint: str
        ) -> Optional[Tuple[Dict[str, Any], int]]:
    """Read + verify one entry: ``(doc, size_bytes)`` or None.  A
    corrupt entry is DELETED on sight so the caller's re-compile can
    re-serialize a good one in its place."""
    path = entry_path(cache_dir, fingerprint)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return None
    doc = unpack_entry(blob)
    if doc is None:
        logger.warning("compilecache: corrupt entry %s dropped", path)
        delete(cache_dir, fingerprint)
        return None
    return doc, len(blob)


def delete(cache_dir: str, fingerprint: str) -> bool:
    try:
        os.remove(entry_path(cache_dir, fingerprint))
        return True
    except OSError:
        return False


def entries(cache_dir: str) -> List[Dict[str, Any]]:
    """List the store's entries: ``[{"name", "size"}...]`` sorted by
    name.  Names are fingerprints + :data:`SUFFIX`."""
    out: List[Dict[str, Any]] = []
    try:
        names = os.listdir(cache_dir)
    except OSError:
        return out
    for fn in sorted(names):
        if not fn.endswith(SUFFIX):
            continue
        try:
            size = os.path.getsize(os.path.join(cache_dir, fn))
        except OSError:
            continue
        out.append({"name": fn, "size": size})
    return out


def total_bytes(cache_dir: str) -> int:
    return sum(e["size"] for e in entries(cache_dir))


def file_digest(path: str) -> Optional[str]:
    """sha256 of an entry FILE's bytes — the fleet transport digest
    (distinct from the in-file payload digest, which covers only the
    pickle body)."""
    h = hashlib.sha256()
    try:
        with open(path, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
    except OSError:
        return None
    return h.hexdigest()
