"""Shape-keyed block/grid autotuner for the Pallas kernel family.

The static ``next_pow2`` clamp that seeded the ``rolann_stats`` wrappers
picks one block size per sample count regardless of how the kernel actually
performs on the running backend.  This module replaces it with a measured
sweep: candidate block sizes are timed per (kernel kind, shape bucket) and
the winners are persisted to a committed per-backend cache
(``kernels/autotune_cache.json``), so every machine that checks the repo out
starts from the last recorded measurement instead of a guess.

Cache format (one file, one JSON object)::

    {
      "version": 1,
      "platforms": {
        "<jax.default_backend()>": {
          "preferred_backend": "einsum" | "fused",
          "blocks": {"<kind>:n<2^a>:m<2^b>:o<2^c>": <block_n>, ...}
        }
      }
    }

Shape keys bucket every dimension to its next power of two, so a cache
tuned at n=4096 also answers n=3000 (same padded tile work).  Lookups are
strictly validated — a corrupt file, a wrong version, or a stale entry
(non-integer, non-power-of-two, out of range) falls back to the static
heuristic with a one-time warning rather than poisoning kernel launches.

``stats_backend.producer()`` consults :func:`preferred_backend` — the
measured einsum-vs-fused verdict recorded by ``benchmarks/kernel_autotune.py``
— so the fused path flips on automatically exactly where it measured faster.

Regenerating on new hardware::

    PYTHONPATH=src python benchmarks/kernel_autotune.py --write-cache

(see docs/kernels.md for the full walkthrough).
"""
from __future__ import annotations

import json
import os
import warnings
from pathlib import Path

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE_PATH = Path(__file__).resolve().parent / "autotune_cache.json"
CACHE_VERSION = 1

#: Candidate sample-axis blocks the sweep measures.  Wider than the old
#: static 512 cap on purpose: whether 1024 pays for its VMEM pressure is
#: exactly the question a measurement answers.
CANDIDATE_BLOCKS = (128, 256, 512, 1024)
_MAX_BLOCK = 4096

#: Gram-stats producers a cache may prefer.  Mirrors
#: ``stats_backend.PRODUCERS`` — spelled out here because ``stats_backend``
#: imports this module to choose its producer (no import cycle).
_KNOWN_BACKENDS = ("einsum", "fused")

# In-memory copy of the cache file, loaded once per (path, process) and
# droppable via `clear_cache()` (tests point $REPRO_AUTOTUNE_CACHE at
# fixtures and must re-read).
_cache: dict | None = None
_cache_src: str | None = None
_warned: set[str] = set()


def next_pow2(x: int) -> int:
    """Smallest power of two >= x (1 for x <= 1)."""
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


def static_block_n(n: int) -> int:
    """The pre-autotune heuristic: pow2-of-n clamped to [128, 512].

    This is both the cache-miss fallback and the corrupt-cache escape: it
    never exceeds 512 (bounded VMEM) and never pads fewer than 128 lanes.
    """
    return max(128, min(next_pow2(n), 512))


def cache_path() -> Path:
    """Active cache file: ``$REPRO_AUTOTUNE_CACHE`` override or the
    committed default next to this module."""
    override = os.environ.get(CACHE_ENV)
    return Path(override) if override else DEFAULT_CACHE_PATH


def _warn_once(key: str, message: str) -> None:
    if key not in _warned:
        _warned.add(key)
        warnings.warn(message, RuntimeWarning, stacklevel=3)


def clear_cache() -> None:
    """Drop the in-memory cache (and warning dedup) so the next lookup
    re-reads the file — the hook tests use after swapping the cache path."""
    global _cache, _cache_src
    _cache = None
    _cache_src = None
    _warned.clear()


def load_cache(path: str | Path | None = None) -> dict:
    """The parsed cache object ({} when missing/corrupt, with a warning).

    Loaded once per process per path; corruption (bad JSON, wrong version,
    non-dict layout) degrades to an empty cache — kernel launches then use
    :func:`static_block_n` and ``"auto"`` resolves to einsum, so a broken
    file can slow things down but never break them.
    """
    global _cache, _cache_src
    p = Path(path) if path is not None else cache_path()
    if _cache is not None and _cache_src == str(p):
        return _cache
    loaded: dict = {}
    if p.exists():
        try:
            raw = json.loads(p.read_text())
            if not isinstance(raw, dict):
                raise ValueError(f"top level is {type(raw).__name__}, not an object")
            if raw.get("version") != CACHE_VERSION:
                raise ValueError(
                    f"cache version {raw.get('version')!r} != {CACHE_VERSION}"
                )
            if not isinstance(raw.get("platforms", {}), dict):
                raise ValueError("'platforms' is not an object")
            loaded = raw
        except (ValueError, OSError) as e:
            _warn_once(
                f"corrupt:{p}",
                f"autotune cache {p} is unreadable ({e}); falling back to "
                "the static block heuristic — regenerate with "
                "benchmarks/kernel_autotune.py --write-cache",
            )
            loaded = {}
    _cache, _cache_src = loaded, str(p)
    return loaded


def _default_platform() -> str:
    import jax

    return jax.default_backend()


def shape_key(kind: str, *, n: int, m: int, o: int) -> str:
    """Bucketed cache key for one kernel launch shape."""
    return f"{kind}:n{next_pow2(n)}:m{next_pow2(m)}:o{next_pow2(o)}"


def lookup_block(
    kind: str, *, n: int, m: int, o: int, platform: str | None = None
) -> int | None:
    """Cached block_n for this (platform, kind, shape bucket), or None.

    Stale/invalid entries (non-int, out of [1, 4096], not a power of two)
    are rejected with a one-time warning so a hand-edited or outdated cache
    degrades to the heuristic instead of crashing a launch.
    """
    plat = platform if platform is not None else _default_platform()
    entry = load_cache().get("platforms", {}).get(plat, {})
    blocks = entry.get("blocks", {}) if isinstance(entry, dict) else {}
    key = shape_key(kind, n=n, m=m, o=o)
    if key not in blocks:
        return None
    b = blocks[key]
    if not isinstance(b, int) or isinstance(b, bool) or not (
        1 <= b <= _MAX_BLOCK and b == next_pow2(b)
    ):
        _warn_once(
            f"stale:{plat}:{key}",
            f"autotune cache entry {key!r} = {b!r} for platform {plat!r} is "
            "invalid (want a power-of-two int in "
            f"[1, {_MAX_BLOCK}]); using the static heuristic — regenerate "
            "with benchmarks/kernel_autotune.py --write-cache",
        )
        return None
    return b


def best_block_n(
    kind: str, *, n: int, m: int, o: int, platform: str | None = None
) -> int:
    """The block_n a kernel wrapper should use when the caller passed none:
    the measured cache winner, else :func:`static_block_n`.

    A cached block tuned for the bucket is still clamped to ``next_pow2(n)``
    — padding 130 samples to a 1024 block tuned at n=1024 would do 8x the
    tile work of the 256 block the actual n needs.
    """
    cached = lookup_block(kind, n=n, m=m, o=o, platform=platform)
    if cached is None:
        return static_block_n(n)
    return min(cached, next_pow2(n))


def preferred_backend(platform: str | None = None) -> str:
    """Measured Gram-stats producer for this platform
    (``stats_backend.producer()``'s answer).

    Reads ``platforms.<platform>.preferred_backend`` from the cache;
    anything missing or unrecognized resolves to ``"einsum"`` — the safe
    default on hardware nobody has measured — and says so once per process,
    so an unmeasured accelerator never silently runs the unfused path.
    """
    plat = platform if platform is not None else _default_platform()
    entry = load_cache().get("platforms", {}).get(plat, {})
    pref = entry.get("preferred_backend") if isinstance(entry, dict) else None
    if pref in _KNOWN_BACKENDS:
        return pref
    if pref is not None:
        _warn_once(
            f"pref:{plat}",
            f"autotune cache names unknown preferred_backend {pref!r} for "
            f"platform {plat!r}; the Gram-stats producer is 'einsum'",
        )
    else:
        _warn_once(
            f"unmeasured:{plat}",
            f"autotune cache {cache_path()} has no preferred_backend for "
            f"platform {plat!r}; the Gram-stats producer is 'einsum', "
            "unmeasured — measure with benchmarks/kernel_autotune.py "
            "--write-cache",
        )
    return "einsum"


def update_cache(
    *,
    platform: str,
    blocks: dict[str, int] | None = None,
    preferred: str | None = None,
    path: str | Path | None = None,
) -> dict:
    """Merge measured winners into the cache file (and the in-memory copy).

    ``blocks`` maps :func:`shape_key` strings to winning block sizes;
    ``preferred`` records the einsum-vs-fused verdict.  Existing entries for
    other platforms/keys are preserved — the committed cache accumulates
    one platform at a time as hardware gets measured.
    """
    p = Path(path) if path is not None else cache_path()
    cache = dict(load_cache(p))
    cache["version"] = CACHE_VERSION
    platforms = dict(cache.get("platforms", {}))
    entry = dict(platforms.get(platform, {}))
    if blocks:
        merged = dict(entry.get("blocks", {}))
        merged.update(blocks)
        entry["blocks"] = dict(sorted(merged.items()))
    if preferred is not None:
        if preferred not in _KNOWN_BACKENDS:
            raise ValueError(
                f"preferred backend {preferred!r} not in {_KNOWN_BACKENDS}"
            )
        entry["preferred_backend"] = preferred
    platforms[platform] = entry
    cache["platforms"] = dict(sorted(platforms.items()))
    p.write_text(json.dumps(cache, indent=2, sort_keys=True) + "\n")
    clear_cache()
    load_cache(p)
    return cache


__all__ = [
    "CACHE_ENV",
    "CANDIDATE_BLOCKS",
    "DEFAULT_CACHE_PATH",
    "best_block_n",
    "cache_path",
    "clear_cache",
    "load_cache",
    "lookup_block",
    "next_pow2",
    "preferred_backend",
    "shape_key",
    "static_block_n",
    "update_cache",
]
