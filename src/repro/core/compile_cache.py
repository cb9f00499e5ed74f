"""Persistent on-disk compile cache: production cold-start skips
re-tracing.

The in-process lowering caches (``cycles._trace_cached``,
``trace_engine._compile_cached`` and the megakernel plan/runner caches)
make repeated launches free *within* one process — but a fresh process
re-walks every program trace and re-decodes every schedule before the
first wave runs. This module adds the missing tier: a content-addressed
pickle store on disk, keyed by a sha256 over

    (format version, artifact kind, program words, SMConfig fields,
     backend, engine)

so a production cold start loads the host-side lowering artifacts
(``ProgramTrace`` walks and decoded schedule columns) instead of
recomputing them. Two artifact kinds ship: ``"trace"`` (the issued-trace
walk, consulted by ``cycles.program_trace``) and ``"lowering"`` (the
pre-decoded schedule columns, consulted by
``trace_engine._compile_cached``); both are backend/engine-independent,
so those key components are fixed tags — backend/engine-*dependent*
compiled artifacts are covered by JAX's own persistent compilation
cache (``configure_jax_cache``).

The cache is OPT-IN (tests and casual runs must not litter the
filesystem): activate it with ``configure(path)`` or by exporting
``EGPU_CACHE_DIR``. Robustness contract: a corrupt, truncated,
wrong-version or otherwise unreadable entry is a MISS — the caller
re-traces and overwrites the entry; the cache never raises into the
launch path. ``stats()`` exposes hit/miss/error counters so tests and
the cold-start benchmark can prove an entry was actually served.

JAX's persistent compilation cache is a separate tier, switched on by the
launch path (``configure_jax_cache``): it lives where
``JAX_COMPILATION_CACHE_DIR`` says, and otherwise at ``DEFAULT_JAX_CACHE``,
one fixed directory in the checkout, so a later process finds what an
earlier one compiled.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
import tempfile
from pathlib import Path

_ENV = "EGPU_CACHE_DIR"
DEFAULT_JAX_CACHE = str(Path(__file__).resolve().parents[3] / ".jax_cache")
_FORMAT = 1
_MAGIC = "egpu-compile-cache"


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    errors: int = 0      # unreadable/corrupt entries (counted as misses)
    stores: int = 0

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class CompileCache:
    """One on-disk cache directory of pickled lowering artifacts."""

    def __init__(self, path: str):
        self.path = str(path)
        self.stats = CacheStats()
        os.makedirs(self.path, exist_ok=True)

    def _file(self, key: str) -> str:
        return os.path.join(self.path, key[:2], key + ".pkl")

    def get(self, key: str):
        """The cached value, or None on miss. ANY failure to read or
        validate the entry — missing file, truncated pickle, foreign
        format, version skew, key collision — is a miss: the caller
        recomputes and ``put`` overwrites the bad entry."""
        f = self._file(key)
        try:
            with open(f, "rb") as fh:
                entry = pickle.load(fh)
            if (not isinstance(entry, dict)
                    or entry.get("magic") != _MAGIC
                    or entry.get("format") != _FORMAT
                    or entry.get("key") != key):
                raise ValueError("malformed cache entry")
        except FileNotFoundError:
            self.stats.misses += 1
            return None
        except Exception:
            self.stats.errors += 1
            self.stats.misses += 1
            try:
                os.unlink(f)             # quarantine: next run rewrites
            except OSError:
                pass
            return None
        self.stats.hits += 1
        return entry["value"]

    def put(self, key: str, value) -> None:
        """Atomically persist ``value``; failures are silent (the cache
        is an accelerator, never a correctness dependency)."""
        f = self._file(key)
        try:
            os.makedirs(os.path.dirname(f), exist_ok=True)
            blob = pickle.dumps({"magic": _MAGIC, "format": _FORMAT,
                                 "key": key, "value": value},
                                protocol=pickle.HIGHEST_PROTOCOL)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(f),
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as fh:
                    fh.write(blob)
                os.replace(tmp, f)       # atomic on POSIX
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
            self.stats.stores += 1
        except Exception:
            pass


# the active cache (None = disabled); resolved lazily from the env so
# `import repro` alone never touches the filesystem
_active: CompileCache | None = None
_resolved = False


def key_for(kind: str, words, cfg, *, backend: str = "-",
            engine: str = "-") -> str:
    """Content hash of one artifact: (version, kind, program words,
    SMConfig, backend, engine). ``cfg`` may be an SMConfig or any object
    with a deterministic repr; backend/engine default to fixed tags for
    backend-independent artifacts."""
    h = hashlib.sha256()
    h.update(repr((_FORMAT, kind, tuple(int(w) for w in words),
                   repr(cfg), backend, engine)).encode())
    return h.hexdigest()


def configure(path: str | None) -> CompileCache | None:
    """Activate the cache at ``path`` (None disables it)."""
    global _active, _resolved
    _resolved = True
    if path is None:
        _active = None
        return None
    _active = CompileCache(path)
    return _active


def configure_jax_cache() -> None:
    """Give JAX's persistent compilation cache its directory.

    ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or a directory
    already set through ``jax.config`` wins; otherwise the cache goes to
    ``DEFAULT_JAX_CACHE``. Called by every launch, so importing the
    package configures nothing."""
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_JAX_CACHE)


def active() -> CompileCache | None:
    """The configured cache, resolving ``EGPU_CACHE_DIR`` on first use."""
    global _resolved
    if not _resolved:
        _resolved = True
        env = os.environ.get(_ENV, "").strip()
        if env:
            configure(env)
    return _active


def load(key: str):
    cc = active()
    return cc.get(key) if cc is not None else None


def store(key: str, value) -> None:
    cc = active()
    if cc is not None:
        cc.put(key, value)


def stats() -> dict | None:
    cc = active()
    return cc.stats.as_dict() if cc is not None else None
