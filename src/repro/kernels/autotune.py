"""Tile-size selection for the Pallas DBB kernels: heuristic table + cache.

Replaces the old per-call ``_pick`` divisor walk with three layers, checked
in order:

1. **Benchmark cache** — exact ``(kind, M, K, N, NNZ, BZ)`` hits from a
   previous :func:`autotune` sweep (in-process dict, optionally persisted
   to JSON via ``REPRO_AUTOTUNE_CACHE=<path>``).
2. **Heuristic table** — MXU-aligned defaults keyed on problem size class
   (the shapes the serving/benchmarks hot paths actually see).
3. **Divisor fallback** — the largest aligned divisor, so any shape still
   gets a legal tiling.

``autotune()`` runs a real timing sweep (only when ``REPRO_AUTOTUNE=1`` or
called explicitly, e.g. from ``benchmarks/kernel_bench.py``) and records
the winner, so the table improves from measured data rather than folklore.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Callable, Dict, Iterable, Optional, Tuple

Tiles = Tuple[int, int, int]  # (tm, tk, tn)

# (kind, m, k, n, nnz, bz) -> (tm, tk, tn)
_CACHE: Dict[Tuple, Tiles] = {}
_CACHE_LOADED = False


def _cache_path() -> Optional[str]:
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def _load_cache() -> None:
    global _CACHE_LOADED
    if _CACHE_LOADED:
        return
    _CACHE_LOADED = True
    path = _cache_path()
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                raw = json.load(f)
            for k, v in raw.items():
                _CACHE[tuple(json.loads(k))] = tuple(v)
        except (OSError, ValueError):
            pass  # a corrupt cache must never break the kernels


def _save_cache() -> None:
    path = _cache_path()
    if not path:
        return
    try:
        with open(path, "w") as f:
            json.dump({json.dumps(list(k)): list(v) for k, v in _CACHE.items()}, f)
    except OSError:
        pass


# Mosaic's block rule: the last two dims of every block are multiples of
# (8, 128) — sublanes, lanes — or span the whole array axis.
SUBLANE, LANE = 8, 128


def largest_divisor(t: int, n: int, step: int = 1) -> int:
    """Largest multiple of ``step`` that divides ``n`` and is <= ``t``."""
    c = min(t, n)
    c -= c % step
    while c > step and n % c != 0:
        c -= step
    return max(c, min(step, n))


def aligned_divisor(t: int, n: int, align: int) -> int:
    """Largest multiple of ``align`` that divides ``n`` and is <= ``t``;
    ``n`` itself when there is none (a block spanning the whole axis is
    always legal).  ``largest_divisor(1024, 12800, 8)`` is 800, which no
    lane dim may be; ``aligned_divisor(1024, 12800, 128)`` is 640."""
    c = min(t, n)
    c -= c % align
    while c >= align:
        if n % c == 0:
            return c
        c -= align
    return n


def heuristic_tiles(m: int, k: int, n: int, bz: int, int8: bool = False) -> Tiles:
    """MXU-aligned default tiling for an ``[M,K] x [K,N]`` DBB matmul.

    Every tile is a legal Mosaic block at any shape: TK and TN are lane
    dims (multiples of 128, or the whole axis), and TM is a lane dim of
    the A/W kernels' transposed activation tile, so it is a multiple of
    128 or the whole (decode-sized) M.  TK holds whole BZ blocks, and the
    combined VMEM working set (x-tile + expanded w-tile + acc) stays
    small enough to double-buffer (~<4 MiB at f32).  INT8 wire tiles
    carry 1-byte values, so the same VMEM budget affords a 2× wider K
    tile — more accumulation per flush.
    """
    # Prefer big N tiles (lane dim) while K is large enough to amortize.
    tn = aligned_divisor(256 if n >= 256 and k <= 2048 else LANE, n, LANE)
    tm = aligned_divisor(LANE, m, LANE)
    # K tile: whole blocks, bounded so x+w tiles fit comfortably in VMEM.
    tk = aligned_divisor(1024 if int8 else 512, k, math.lcm(LANE, bz))
    return tm, tk, tn


def get_tiles(
    m: int,
    k: int,
    n: int,
    nnz: int,
    bz: int,
    kind: str = "w",
) -> Tiles:
    """Resolve the tiling: benchmark cache first, then heuristic.

    ``kind`` ∈ {``w``, ``aw``, ``w_int8``, ``aw_int8``} — int8 wire
    formats get their own cache keys and a wider-K heuristic.
    """
    _load_cache()
    hit = _CACHE.get((kind, m, k, n, nnz, bz))
    if hit is not None:
        return hit
    return heuristic_tiles(m, k, n, bz, int8=kind.endswith("int8"))


def candidate_tiles(m: int, k: int, n: int, bz: int) -> Iterable[Tiles]:
    """Legal (divisor-aligned) candidate tilings for an autotune sweep."""
    tms = sorted({largest_divisor(t, m, 1) for t in (8, 32, 128, 256, m)})
    tks = sorted({largest_divisor(t, k, bz) for t in (bz * 8, 256, 512, 1024, k)})
    tns = sorted({largest_divisor(t, n, 1) for t in (128, 256, 512, n)})
    seen = set()
    for tm in tms:
        for tk in tks:
            if tk % bz:
                continue
            for tn in tns:
                # skip tilings whose working set clearly blows VMEM (~16MB)
                vmem_f32 = (tm * tk + tk * tn + tm * tn) * 4
                if vmem_f32 > 8 * 1024 * 1024:
                    continue
                c = (tm, tk, tn)
                if c not in seen:
                    seen.add(c)
                    yield c


def autotune(
    run: Callable[[Tiles], Callable[[], object]],
    m: int,
    k: int,
    n: int,
    nnz: int,
    bz: int,
    kind: str = "w",
    reps: int = 3,
) -> Tiles:
    """Time every candidate tiling and cache the winner.

    ``run(tiles)`` returns a nullary callable executing the kernel with
    that tiling (already closed over the operands); it is invoked once for
    warmup/compile and ``reps`` times for timing.  Falls back to the
    heuristic for candidates that fail to compile.
    """
    import jax

    _load_cache()
    key = (kind, m, k, n, nnz, bz)
    if key in _CACHE:
        return _CACHE[key]
    best, best_t = None, float("inf")
    for tiles in candidate_tiles(m, k, n, bz):
        try:
            fn = run(tiles)
            jax.block_until_ready(fn())  # compile + warmup
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn())
            dt = (time.perf_counter() - t0) / reps
        except Exception:  # illegal tiling for this backend: skip
            continue
        if dt < best_t:
            best, best_t = tiles, dt
    if best is None:
        # every candidate failed (e.g. no TPU on this host): fall back to
        # the heuristic WITHOUT caching it, so a later sweep on capable
        # hardware isn't blocked by a folklore entry under this key
        return heuristic_tiles(m, k, n, bz, int8=kind.endswith("int8"))
    _CACHE[key] = best
    _save_cache()
    return best


def autotune_enabled() -> bool:
    return os.environ.get("REPRO_AUTOTUNE", "0") == "1"


# ------------------------------------------------- paged-attention kind
#
# The paged decode-attention kernel (kernels/paged_attn.py) has no free
# tile dimensions — its blocks are pinned by (page_size, head_dim) — so
# its tunable is the *implementation*: "gather" (paged_read + mha, the
# XLA-fused jnp path) vs "fused" (the in-kernel page-table walk).  The
# choice shares the same three-layer resolution as the tile kinds:
# benchmark cache (exact shape hit, persisted via REPRO_AUTOTUNE_CACHE)
# → backend heuristic → gather.  Cache keys reuse the 6-tuple layout
# ((kind, b, sg, ps, dk, 0)) so one JSON file serves both kinds; values
# are 1-tuples of the impl name.

PAGED_ATTN_IMPLS = ("gather", "fused")


def heuristic_paged_attn_impl(backend: Optional[str] = None) -> str:
    """Backend heuristic: the Pallas walk wins on TPU (it exists to cut
    HBM traffic the XLA gather path must pay); on CPU the kernel only
    runs through the interpreter, so the jnp gather path stays the
    default — "fused" remains available explicitly (tests/CI parity)."""
    if backend is None:
        import jax

        backend = jax.default_backend()
    return "fused" if backend == "tpu" else "gather"


def get_paged_attn_impl(b: int, sg: int, ps: int, dk: int) -> str:
    """Resolve the paged-attention implementation for a problem shape:
    benchmark cache first, then the backend heuristic.

    The cache key carries no backend, so a ``"fused"`` verdict is only
    honored where the compiled kernel actually runs (TPU): replaying a
    TPU-tuned cache file on a CPU/GPU host must not route ``"auto"``
    serving through the Pallas interpreter.  ``"gather"`` hits are
    backend-agnostic (the jnp path runs everywhere).
    """
    _load_cache()
    hit = _CACHE.get(("paged_attn", b, sg, ps, dk, 0))
    if hit is not None and hit[0] in PAGED_ATTN_IMPLS:
        if hit[0] != "fused":
            return hit[0]
        import jax

        if jax.default_backend() == "tpu":
            return hit[0]
    return heuristic_paged_attn_impl()


def autotune_paged_attn(
    run: Callable[[str], Callable[[], object]],
    b: int,
    sg: int,
    ps: int,
    dk: int,
    reps: int = 3,
) -> str:
    """Time gather vs fused for one shape and cache the winner.

    ``run(impl)`` returns a nullary callable executing that
    implementation (closed over the operands) — same contract as
    :func:`autotune`.  The winner is cached only when EVERY candidate
    ran: the cache key carries no backend, so a partial sweep (e.g. a
    CPU host where the compiled kernel cannot run) must answer from the
    heuristic without persisting — otherwise a CPU-produced cache file
    would pin "gather" on a later TPU host, the same capable-host rule
    :func:`autotune` applies to failed tile sweeps.
    """
    import jax

    _load_cache()
    key = ("paged_attn", b, sg, ps, dk, 0)
    if key in _CACHE and _CACHE[key][0] in PAGED_ATTN_IMPLS:
        return _CACHE[key][0]
    best, best_t, timed = None, float("inf"), 0
    for impl in PAGED_ATTN_IMPLS:
        try:
            fn = run(impl)
            jax.block_until_ready(fn())  # compile + warmup
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fn())
            dt = (time.perf_counter() - t0) / reps
        except Exception:  # impl unavailable on this backend: skip
            continue
        timed += 1
        if dt < best_t:
            best, best_t = impl, dt
    if timed < len(PAGED_ATTN_IMPLS):
        # incomplete comparison: don't let this host's limitation become
        # a cached verdict for a capable one
        return best if best is not None else heuristic_paged_attn_impl()
    _CACHE[key] = (best,)
    _save_cache()
    return best
