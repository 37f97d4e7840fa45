"""Persistent XLA executable cache — the compilation service's disk tier.

Moved out of the package ``__init__`` when the compilation service landed:
the on-disk executable cache, the signature manifest (:mod:`.manifest`)
and AOT warm-start (:mod:`.service`) are one subsystem sharing one
layout under :func:`base_dir`::

    <base>/
        host-<isa-tag>/         jax persistent compilation cache entries
        manifests/*.jsonl       signature manifests (replayable journals)
        exported/*.shlo         exported StableHLO blobs (trace-skip tier)

``<base>`` is ``MXNET_XLA_CACHE_DIR`` when set, else the fixed path
``<checkout>/.cache/mxnet_tpu_xla`` (git-ignored; the path is part of
jax's cache key, so it never moves with a pid, a clock or a temp dir).

Where the environment hands the process a cache — ``JAX_COMPILATION_
CACHE_DIR``, which jax itself reads — that directory IS the layout:
executables sit directly in it, manifests/ and exported/ under it, and
this module neither points jax anywhere else nor deletes anything there
(its owner decides what it holds).

Reference counterpart: MXNet's op-level autotune caches / CUDA kernel
cache. Training-step executables for transformer-sized models take
minutes to build; caching them on disk makes the second process start in
seconds — and the manifest replays the *set of signatures* so the disk
hits happen before first traffic, not during it.

Knobs:
* ``MXNET_XLA_CACHE``            — 0 disables (default: on for
  TPU-capable processes, off for pure-CPU ones, see ``_cache_default``);
* ``MXNET_XLA_CACHE_DIR``        — base directory override (ignored when
  ``JAX_COMPILATION_CACHE_DIR`` is set);
* ``MXNET_XLA_CACHE_MIN_COMPILE_S`` — only persist executables whose
  compile took at least this long (default 1.0; benches set 0 so CPU
  compiles persist too);
* ``MXNET_XLA_CACHE_MAX_BYTES``  — size cap for this host's namespace;
  oldest-used entries are GC'd past it at setup (default 4 GiB, 0 = no GC).

Our own cache is namespaced per host-CPU feature set: jax's cache key does
not include host ISA features, so an XLA:CPU AOT executable compiled on
an AVX-512/AMX host replays on a host without them ("could lead to
execution errors such as SIGILL" — cpu_aot_loader). A host with a
different /proc/cpuinfo flag set gets its own subdirectory and
recompiles.
"""
from __future__ import annotations

import logging
import os
import re
from typing import Optional

_log = logging.getLogger(__name__)

__all__ = ["setup", "cache_dir", "base_dir", "gc_cache", "stats"]

_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "mxnet_tpu_xla")

# ISA-extension prefixes (x86 `flags` / ARM `Features`) that codegen can
# actually depend on; kernel-mitigation and power-management flags
# (md_clear, ibrs, retbleed, ...) churn with microcode/kernel updates and
# must not key the cache — they'd force full recompiles on identical
# hardware.
_ISA_PREFIXES = (
    "sse", "avx", "amx", "fma", "bmi", "aes", "sha", "mmx", "f16c",
    "pclmul", "vpclmul", "gfni", "vaes", "adx", "lzcnt", "popcnt", "abm",
    "movbe", "movdir", "xsave", "rtm", "rdrnd", "rdseed", "rdpid",
    "fsgsbase", "invpcid", "clflush", "clwb", "cldemote", "wbnoinvd",
    "serialize", "cmov", "cx8", "cx16", "fxsr", "crc32",
    "lahf", "kl", "widekl", "waitpkg", "enqcmd", "uintr", "hreset", "lm",
    "neon", "asimd", "sve", "fp", "fphp", "crypto", "atomics", "lse",
)
# deliberately absent: rtm/hle/tsxldtrk — TSX is routinely disabled by
# microcode mitigations (flag churn on identical hardware) and XLA codegen
# never emits it.

# exact filenames the jax compilation cache writes
# (<fn>-<sha256 hex>-cache plus its -atime sidecar)
_jax_cache_entry = re.compile(r".+-[0-9a-f]{64}-(cache|atime)$").fullmatch

_cache_dir: Optional[str] = None


def _host_cpu_tag() -> str:
    import hashlib
    import platform

    feats = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    toks = line.split(":", 1)[1].split()
                    feats = " ".join(
                        sorted(t for t in toks if t.startswith(_ISA_PREFIXES)))
                    break
    except OSError:
        pass
    if not feats:
        # degraded path (no readable /proc/cpuinfo — non-Linux or /proc
        # unmounted): only the coarse arch is known, so hosts of the same
        # arch but different ISA extensions share a namespace and the
        # cross-host AOT protection is WEAK here; the distinct prefix
        # keeps these entries out of any verified-feature namespace.
        feats = "weak:" + (platform.processor() or platform.machine()
                           or "unknown")
    return hashlib.sha1(feats.encode()).hexdigest()[:12]


def _cache_default() -> str:
    # Pure-CPU processes (tests, the driver's virtual-mesh dryrun) default
    # to NO persistent cache: their compiles are cheap, and XLA:CPU AOT
    # entries are what trigger the cpu_aot_loader feature-probe warning on
    # every later load (the probe doesn't know the +prefer-no-scatter/
    # +prefer-no-gather tuning pseudo-features this XLA version compiles
    # with — benign same-host noise, but it pollutes driver artifacts and
    # reads like SIGILL risk). TPU-capable processes keep the cache (the
    # minutes-long transformer TrainStep compiles are the whole point);
    # their host-side CPU jits stay under the min-compile-time bar, so
    # no CPU AOT entries get written and the warning cannot fire.
    from ..base import cpu_only_process

    return "0" if cpu_only_process() else "1"


def _handed_dir() -> Optional[str]:
    """The cache directory the environment placed, if any."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or None


def base_dir() -> str:
    """Root of the on-disk layout (see the module docstring)."""
    return _handed_dir() or os.environ.get("MXNET_XLA_CACHE_DIR",
                                           _REPO_CACHE)


def cache_dir() -> Optional[str]:
    """The directory this process's executables persist in, or None when
    the disk tier is disabled."""
    return _cache_dir


def setup() -> Optional[str]:
    """Configure jax's persistent compilation cache under the namespaced
    layout; run once at package import. Returns the active cache dir (or
    None when disabled). Best-effort: an unwritable directory degrades to
    in-memory-only compilation, never an import error."""
    global _cache_dir

    if os.environ.get("MXNET_XLA_CACHE", _cache_default()) == "0":
        return None
    import jax

    try:
        min_s = float(os.environ.get(
            "MXNET_XLA_CACHE_MIN_COMPILE_S", "1.0"))
    except ValueError:
        min_s = 1.0
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_s)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    handed = _handed_dir()
    if handed:
        # jax read the variable itself: set no directory in code, add no
        # sub-directory, delete nothing in a directory we were handed
        _cache_dir = handed
        return _cache_dir
    target = os.path.join(base_dir(), "host-" + _host_cpu_tag())
    try:
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
        _cache_dir = target
        gc_cache()
    except Exception:  # pragma: no cover - cache is best-effort
        _cache_dir = None
    return _cache_dir


def _gc_exported(exported_dir: str, max_bytes: int) -> int:
    """LRU sweep of the exported-StableHLO blob store (the trace-skip
    tier lives beside the host namespaces and must honor the same size
    cap, or blobs accumulate per signature forever)."""
    try:
        names = [f for f in os.listdir(exported_dir)
                 if f.endswith(".shlo")]
    except OSError:
        return 0
    blobs = []
    total = 0
    for f in names:
        p = os.path.join(exported_dir, f)
        try:
            st = os.stat(p)
        except OSError:
            continue
        blobs.append((st.st_mtime, st.st_size, p))
        total += st.st_size
    removed = 0
    for _, size, p in sorted(blobs):
        if total <= max_bytes:
            break
        try:
            os.unlink(p)
        except OSError:
            continue
        total -= size
        removed += 1
        _log.debug("exported blob gc: evicted %s (%d bytes)", p, size)
    return removed


def stats(directory: Optional[str] = None) -> dict:
    """Entry count + total bytes of one cache namespace."""
    d = directory or _cache_dir
    n = size = 0
    if d:
        try:
            for f in os.listdir(d):
                p = os.path.join(d, f)
                if _jax_cache_entry(f) and os.path.isfile(p):
                    n += 1
                    size += os.path.getsize(p)
        except OSError:
            pass
    return {"dir": d, "entries": n, "bytes": size}


def gc_cache(max_bytes: Optional[int] = None,
             directory: Optional[str] = None) -> int:
    """Size-capped GC of the persistent executable tier: delete
    least-recently-used entries (jax maintains an ``-atime`` sidecar per
    entry; its mtime is the entry's last use) until the namespace fits
    ``max_bytes``. Returns the number of entries removed. Never sweeps
    a directory the environment handed the process (its owner's call)."""
    d = directory or _cache_dir
    if not d or (directory is None and _handed_dir()):
        return 0
    if max_bytes is None:
        try:
            max_bytes = int(os.environ.get(
                "MXNET_XLA_CACHE_MAX_BYTES", str(4 << 30)))
        except ValueError:
            max_bytes = 4 << 30
    if max_bytes <= 0:
        return 0
    entries = {}   # stem -> {"bytes", "atime", "mtime", "files"}
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    for f in names:
        p = os.path.join(d, f)
        if not (_jax_cache_entry(f) and os.path.isfile(p)):
            continue
        stem = f.rsplit("-", 1)[0]
        e = entries.setdefault(stem, {"bytes": 0, "atime": None,
                                      "mtime": 0.0, "files": []})
        try:
            st = os.stat(p)
        except OSError:
            continue
        e["bytes"] += st.st_size
        e["files"].append(p)
        # the -atime sidecar's mtime is jax's last-use record and WINS;
        # the entry file's own mtime is the fallback when it is absent
        if f.endswith("-atime"):
            e["atime"] = st.st_mtime
        else:
            e["mtime"] = max(e["mtime"], st.st_mtime)
    for e in entries.values():
        e["used"] = e["atime"] if e["atime"] is not None else e["mtime"]
    total = sum(e["bytes"] for e in entries.values())
    removed = 0
    for stem in sorted(entries, key=lambda s: entries[s]["used"]):
        if total <= max_bytes:
            break
        e = entries[stem]
        for p in e["files"]:
            try:
                os.unlink(p)
            except OSError:
                pass
        total -= e["bytes"]
        removed += 1
        _log.debug("xla cache gc: evicted %s (%d bytes)", stem, e["bytes"])
    # the exported-blob tier SHARES the cap (one budget for the whole
    # layout, not one per tier): blobs get whatever the jax-cache
    # namespace left unspent
    removed += _gc_exported(os.path.join(os.path.dirname(d), "exported"),
                            max(0, max_bytes - total))
    if removed:
        try:
            from .. import telemetry
            from ..telemetry import _state as _tstate

            if _tstate.enabled:
                telemetry.record_cache_eviction("xla_persistent", removed)
        except Exception:
            pass
    return removed
