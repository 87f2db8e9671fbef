"""Block-size selection for the Pallas kernels (ROADMAP item 3c, step 1).

Every fused kernel in this package takes its tile sizes as a static
argument; until now they were hard-coded module constants.  This module
centralises the choice behind one function pair:

    ``matmul_block(m, n, k)``    -> (bm, bn, bk)  for ``ops.int8_matmul_fp``
    ``attention_block(sq, skv, hd)`` -> (bq, bkv) for ``ops.int8_attention_fp``

Selection is **heuristic by default** (minimise tile padding waste over a
fixed candidate list, biased toward the historical defaults so existing
shapes keep their exact schedule) and optionally **benchmark-driven**:

    REPRO_TUNE=benchmark      time each candidate once per (kind, shape,
                              dtype) and cache the winner for the process
    REPRO_TUNE=heuristic      the default (no timing)

Hard overrides for experiments / tests, checked before the cache:

    REPRO_MM_BLOCK="bm,bn,bk"     pin the matmul tile
    REPRO_ATTN_BLOCK="bq,bkv"     pin the attention tile

Block choice is *parity-safe* by construction: every kernel using these
tiles does exact integer per-tile arithmetic (or order-pinned fp
recurrences whose schedule is shared with the simulated reference), so a
different block size changes speed, never results.  The benchmark mode
times the real kernel via a caller-supplied thunk; on CPU interpret mode
this mostly measures the interpreter, which is why heuristic is the
default — the benchmark path is for real-TPU lanes.  Every candidate
compiles for a TPU v5e at starcoder2-3b widths
(``tests/test_chip_compile.py``).
"""
from __future__ import annotations

import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

# Historical defaults, kept as the first candidate so unchanged shapes keep
# their exact schedule (and the committed benchmark baselines stay valid).
MATMUL_DEFAULT = (256, 256, 256)
ATTN_DEFAULT = (128, 128)

MATMUL_CANDIDATES: Tuple[Tuple[int, int, int], ...] = (
    MATMUL_DEFAULT,
    (128, 128, 256),
    (128, 256, 256),
    (256, 128, 256),
    (512, 256, 256),
)
# bkv stays a multiple of 128: the kernel's per-key sums block is (1, bkv),
# and the TPU compiler refuses a lane dim below 128 (it refused bkv = 64
# at starcoder2-3b widths).
ATTN_CANDIDATES: Tuple[Tuple[int, int], ...] = (
    ATTN_DEFAULT,
    (64, 128),
    (256, 128),
)

# (kind, shape, dtype) -> chosen block.  Process-lifetime cache: the choice
# must be stable within a run or jit would recompile per call.
_CACHE: Dict[tuple, tuple] = {}


def clear_cache() -> None:
    _CACHE.clear()


def _parse_env(name: str, arity: int) -> Optional[tuple]:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    parts = [p for p in raw.replace(",", " ").split() if p]
    if len(parts) != arity:
        raise ValueError(
            f"{name} must be {arity} comma-separated ints, got {raw!r}")
    vals = tuple(int(p) for p in parts)
    if any(v <= 0 for v in vals):
        raise ValueError(f"{name} entries must be positive, got {raw!r}")
    return vals


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _padding_waste(dims: Sequence[int], block: Sequence[int]) -> float:
    """Fraction of padded tile volume that is outside the real operand."""
    full = 1.0
    padded = 1.0
    for d, b in zip(dims, block):
        eb = min(b, d) if d > 0 else b
        full *= max(d, 1)
        padded *= _cdiv(max(d, 1), eb) * eb
    return (padded - full) / padded


def _heuristic(dims: Sequence[int], candidates, default) -> tuple:
    best = default
    best_waste = _padding_waste(dims, default)
    for cand in candidates:
        w = _padding_waste(dims, cand)
        # Strict improvement required: ties keep the earlier (default-first)
        # candidate, so the historical schedule wins unless a tile strictly
        # reduces padding.
        if w < best_waste - 1e-12:
            best, best_waste = cand, w
    return best


def _benchmark(candidates, thunk: Callable[[tuple], Callable[[], None]],
               default) -> tuple:
    best, best_t = default, float("inf")
    for cand in candidates:
        # A tile the compiler refuses raises here: every candidate must
        # compile, and silently skipping one would hide that it does not.
        run = thunk(cand)
        run()  # warmup / compile
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        if dt < best_t:
            best, best_t = cand, dt
    return best


def _select(kind: str, dims: tuple, dtype, candidates, default,
            bench_thunk: Optional[Callable] = None) -> tuple:
    key = (kind, dims, str(dtype))
    hit = _CACHE.get(key)
    if hit is not None:
        return hit
    mode = os.environ.get("REPRO_TUNE", "heuristic").strip().lower()
    if mode == "benchmark" and bench_thunk is not None:
        choice = _benchmark(candidates, bench_thunk, default)
    else:
        choice = _heuristic(dims, candidates, default)
    _CACHE[key] = choice
    return choice


def matmul_block(m: int, n: int, k: int, dtype="int8",
                 bench_thunk: Optional[Callable] = None
                 ) -> Tuple[int, int, int]:
    """Tile for ``ops.int8_matmul_fp``.  Env ``REPRO_MM_BLOCK`` wins."""
    override = _parse_env("REPRO_MM_BLOCK", 3)
    if override is not None:
        return override
    return _select("matmul", (m, n, k), dtype, MATMUL_CANDIDATES,
                   MATMUL_DEFAULT, bench_thunk)


def attention_block(sq: int, skv: int, hd: int, dtype="int8",
                    bench_thunk: Optional[Callable] = None
                    ) -> Tuple[int, int]:
    """(bq, bkv) for the fused attention kernel.  Env ``REPRO_ATTN_BLOCK``
    wins.  The choice is made once at dispatch and shared by BOTH backends
    (the simulated reference replays the identical block schedule), so
    tuning cannot break the bit-parity contract."""
    override = _parse_env("REPRO_ATTN_BLOCK", 2)
    if override is not None:
        return override
    # Padding heuristic over the (sq, skv) tiling; head_dim rides along
    # untiled but participates in the cache key (different hd => different
    # arithmetic intensity on real hardware).
    return _select("attention", (sq, skv, hd), dtype, ATTN_CANDIDATES,
                   ATTN_DEFAULT, bench_thunk)
