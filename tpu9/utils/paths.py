"""Shared single-path-component validation.

One definition for every place a tenant-supplied name becomes a filesystem
path segment (volume mounts, disk dirs, CLI destinations) — the defenses
must tighten in lockstep, not diverge per call site.
"""

from __future__ import annotations

import os


def repo_root() -> str:
    """The checkout root (parent of the tpu9 package)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """JAX's persistent compile cache — the ONE rule every process follows:
    ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
    ``<checkout>/.cache/xla``. The path is part of the cache key, so it is
    never derived from a temp name, a pid or a time."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        repo_root(), ".cache", "xla")


def native_binary(name: str) -> str:
    """Path of a built native component (native/build/<name>) — the ONE
    definition every consumer (runtimes, lifecycle, cachefs, CLI) uses, so
    relocating the build dir is a single edit. Callers check existence;
    missing binaries degrade per-feature."""
    return os.path.join(repo_root(), "native", "build", name)


def validate_path_part(part: str, what: str = "path part") -> str:
    """Reject anything that could traverse outside its parent directory
    when joined as a single component."""
    if (not part or "/" in part or "\\" in part or "\x00" in part
            or part in (".", "..")):
        raise ValueError(f"invalid {what}: {part!r}")
    return part
