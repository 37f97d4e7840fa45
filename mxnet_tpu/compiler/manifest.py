"""Signature manifest — the on-disk journal of everything this process
compiled, replayable by :func:`mxnet_tpu.compiler.warm_start`.

Format: append-only JSONL. One object per line::

    {"v": 1, "site": "train_step", "fp": "<hex>", "spec": <tagged tree>}

``spec`` is the site's replay recipe (op name + attrs + avals for
``eager_op``, the node program for ``fused_segment``, graph ident + input
signatures for ``cached_op``/``train_step``), encoded with the tagged
tuple codec in :mod:`.keys` so it round-trips to exactly the tuples the
live cache keys compare against.

Durability: the file is created through ``checkpoint.atomic_write``
(write-temp + fsync + rename); each further record appends ONE fsynced
line. A crash mid-append can tear at most that line, and reading
tolerates torn/corrupt lines (plus hand edits, unknown sites, and
version-mismatched entries) — each is skipped and counted, not fatal:
a stale manifest warms less, it never breaks startup.

Location: ``MXNET_COMPILE_MANIFEST`` names the file (``1`` = the default
``<MXNET_XLA_CACHE_DIR>/manifests/signatures.jsonl``, sharing the
persistent XLA cache's base layout; ``0``/unset = recording off).
"""
from __future__ import annotations

import json
import logging
import os
import threading
from typing import Dict, List, Optional

from . import keys

__all__ = ["Manifest", "default_path", "recorder", "enable_recording",
           "disable_recording", "record_signature", "KNOWN_SITES",
           "MANIFEST_VERSION"]

_log = logging.getLogger(__name__)

MANIFEST_VERSION = 1

# sites warm_start knows how to handle; an entry whose site is absent here
# is stale (written by a newer/older build) and is skipped on load
KNOWN_SITES = ("eager_op", "fused_segment", "cached_op", "train_step",
               "executor", "optimizer_sweep")


def default_path() -> str:
    from . import persistent

    return os.path.join(persistent.base_dir(), "manifests",
                        "signatures.jsonl")


class Manifest:
    """One signature journal file: load-tolerant reader + atomic recorder."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_path()
        self._lock = threading.Lock()
        self._entries: Optional[List[Dict]] = None   # loaded lazily
        self._fps = set()
        self.n_skipped = 0          # corrupt/stale lines seen at load

    # -- read ----------------------------------------------------------
    def _load_locked(self) -> List[Dict]:
        if self._entries is not None:
            return self._entries
        entries: List[Dict] = []
        self.n_skipped = 0
        try:
            with open(self.path, encoding="utf-8") as f:
                lines = f.readlines()
        except OSError:
            lines = []
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                raw = json.loads(line)
                if (raw.get("v") != MANIFEST_VERSION
                        or raw.get("site") not in KNOWN_SITES
                        or not isinstance(raw.get("fp"), str)):
                    raise ValueError("stale or malformed entry")
                entry = {"v": raw["v"], "site": raw["site"],
                         "fp": raw["fp"],
                         "spec": keys._dec(raw.get("spec"))}
            except Exception:
                self.n_skipped += 1
                continue
            if entry["fp"] in self._fps:
                continue
            self._fps.add(entry["fp"])
            entries.append(entry)
        self._entries = entries
        if self.n_skipped:
            _log.debug("manifest %s: skipped %d corrupt/stale line(s)",
                       self.path, self.n_skipped)
        return entries

    def entries(self) -> List[Dict]:
        with self._lock:
            return list(self._load_locked())

    def __len__(self) -> int:
        return len(self.entries())

    # -- write ---------------------------------------------------------
    def record(self, site: str, spec) -> Optional[str]:
        """Journal one compiled signature; returns its fingerprint, or
        None when it was already journaled (dedupe by fingerprint).

        Durability model: the journal is created (and compacted) through
        ``checkpoint.atomic_write``; subsequent records APPEND one
        fsynced line — O(1) per compile miss, and a torn tail line is
        exactly what the tolerant reader skips. A full rewrite per
        record would re-serialize the whole journal on the compile-miss
        path (O(n²) over a run — round-10 review finding)."""
        fp = keys.fingerprint((site, keys.encode(spec)))
        with self._lock:
            self._load_locked()
            if fp in self._fps:
                return None
            self._fps.add(fp)
            entry = {"v": MANIFEST_VERSION, "site": site, "fp": fp,
                     "spec": spec}
            self._entries.append(entry)
            line = json.dumps(
                {"v": entry["v"], "site": entry["site"],
                 "fp": entry["fp"], "spec": keys._enc(entry["spec"])},
                sort_keys=True) + "\n"
            try:
                os.makedirs(os.path.dirname(self.path) or ".",
                            exist_ok=True)
                if not os.path.exists(self.path):
                    from ..checkpoint import atomic_write

                    atomic_write(self.path, line.encode())
                else:
                    with open(self.path, "a", encoding="utf-8") as f:
                        f.write(line)
                        f.flush()
                        os.fsync(f.fileno())
            except Exception:
                # journaling is best-effort: a read-only cache dir must
                # not break compiles (the entry stays recorded in-memory)
                _log.debug("manifest %s: record failed", self.path,
                           exc_info=True)
        return fp


# ---------------------------------------------------------------------------
# Process-wide recorder: sites call record_signature() on every compile
# miss; it no-ops unless recording was enabled (env or API).
# ---------------------------------------------------------------------------

class _Recorder:
    __slots__ = ("manifest",)

    def __init__(self):
        self.manifest: Optional[Manifest] = None


_recorder = _Recorder()
_recorder_lock = threading.Lock()
_env_checked = False


def _check_env() -> None:
    global _env_checked
    if _env_checked:
        return
    with _recorder_lock:
        if _env_checked:
            return
        spec = os.environ.get("MXNET_COMPILE_MANIFEST", "")
        if spec and spec != "0":
            path = default_path() if spec == "1" else spec
            _recorder.manifest = Manifest(path)
        _env_checked = True


def enable_recording(path: Optional[str] = None) -> Manifest:
    """Start journaling compiled signatures to ``path`` (default: the
    shared cache layout). Returns the live Manifest."""
    global _env_checked
    with _recorder_lock:
        _recorder.manifest = Manifest(path)
        _env_checked = True
        return _recorder.manifest


def disable_recording() -> None:
    global _env_checked
    with _recorder_lock:
        _recorder.manifest = None
        _env_checked = True


def recorder() -> Optional[Manifest]:
    """The active manifest recorder, or None when recording is off."""
    _check_env()
    return _recorder.manifest


def record_signature(site: str, spec) -> None:
    """Journal one compiled signature (no-op when recording is off).
    Called by every cache site on a compile miss."""
    m = recorder()
    if m is None:
        return
    try:
        m.record(site, spec)
    except Exception:
        _log.debug("signature journaling failed for site %s", site,
                   exc_info=True)
