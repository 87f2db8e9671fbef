"""Fused int8 flash-attention with in-kernel hindsight statistics.

This is the paper's Fig. 4 dataflow applied to the transformer's dominant
FLOP consumer.  Attention is a *chain* of two contractions coupled by a
softmax; with dynamic ranges the probability tensor would need a full
min/max reduction between QK^T and PV — serializing the online-softmax
loop and forcing the [sq, skv] score tile out to HBM.  With **in-hindsight
static ranges for q, k, v and the softmax probabilities**, each (q block,
kv block) tile is:

    int8 QK^T (MXU, int32 accumulate)  ->  fp32 online softmax
    -> requantize p with the PRE-COMPUTED [p_lo, p_hi] registers
    -> int8 PV (MXU, int32 accumulate)

entirely in VMEM, while the same resident tile is reduced to the
statistics partials — no second pass, no score tile in HBM.  Which ones is
the schedule's ``stats``: ``"range"`` folds only (min, max), the two that
feed the next step's range update; ``"full"`` (a policy with telemetry on)
adds the (clip, n, err, sig) counters.  Under ``"range"`` slots 2-5 keep
their initial 0, and the err/sig tree sums — the longest serial chain of
a tile — are not traced at all.

Bit-parity contract (the PR-3/PR-5 convention, extended to attention)
---------------------------------------------------------------------
``attention_core_reference`` is an **order-pinned online-softmax
reference** that replays the *identical block schedule and recurrence* as
the Pallas kernel: same (bq, bkv) tiles, same kv visitation order, same
``fence``-pinned mul->add seams, same per-tile pairwise-halving tree sums
for the fp statistics.  Every contraction is exact in int32, every fp
reduction is either exact in any association (min/max, integer-valued
counts) or order-pinned, and the per-block fp recurrence is shared code
(``_scores_to_probs`` / ``_accumulate`` / ``_stats_update``) — so kernel
and reference agree bit-for-bit on outputs, softmax registers and all six
statistics slots, in both ``stats`` modes (slots 2-5 are computed only
under ``"full"``, i.e. with telemetry on).  The two modes share the whole
recurrence: outputs, softmax registers and (min, max) are bit-equal
between them.  ``reduce_pstats`` is the single shared reduction of
the per-(head, q block) partials for BOTH backends.

Layout: q is uint8 ``[BH, sq, hd]`` with ``BH = B * KV * G`` (GQA
head-major flattening); k is int8 ``[ZB, skv, hd]`` and v int8 ``[ZB,
skv, hdv]`` with ``ZB = B * KV`` (the value head dim ``hdv`` may differ
from the query-key one, as in latent attention) — the kernel broadcasts each kv head over its G query heads through
the BlockSpec index map (``bh // G``), so GQA never materializes repeated
k/v.

Registers operand (fp32 ``[1, 8]``, all integral-valued where applicable):
    [zp_q, alpha_qk, scale_p, zp_p, alpha_pv, p_lo, p_hi, spare]
with ``alpha_qk = sm_scale * scale_q * scale_k`` and ``alpha_pv = scale_p
* scale_v`` — computed ONCE at dispatch and shared by both backends.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import QuantSpec

from . import STATS_TILE, platform, stats_tile, stats_values

NEG_INF = -1e30           # matches models/attention.py: finite, NaN-free
P_SPEC = QuantSpec(bits=8, symmetric=False)   # probability grid [0, 255]
STAT_SLOTS = 6            # (pmin, pmax, clip, n, err, sig)
RANGE_SLOTS = 2           # (pmin, pmax): what the range update reads
STAT_MODES = ("range", "full")

MASK_MODES = ("causal", "sliding", "prefix", "cross", "bidir")


# ---------------------------------------------------------------------------
# Schedule: the static block plan of kernel, reference, backward, fp core.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnSchedule:
    """Hashable (static-arg) description of one attention core call."""

    sq: int                # query length
    skv: int               # key/value length
    hd: int                # query-key head dim
    hdv: int               # value head dim (the output's)
    bq: int                # q block rows
    bkv: int               # kv block cols
    groups: int            # G = n_heads // n_kv (GQA broadcast factor)
    mode: str              # causal | sliding | prefix | cross | bidir
    window: int            # sliding window (0 when unused)
    prefix_len: int        # prefix-LM boundary (0 when unused)
    sm_scale: float        # softmax scale (head_dim ** -0.5)
    width: int             # kv blocks walked per q block (the grid's last axis)
    stats: str = "full"    # range: (min, max) only | full: all six slots

    @property
    def nq(self) -> int:
        return -(-self.sq // self.bq)

    @property
    def nkv(self) -> int:
        return -(-self.skv // self.bkv)

    @property
    def stat_slots(self) -> int:
        """Statistics slots each tile computes (the rest stay at 0)."""
        return RANGE_SLOTS if self.stats == "range" else STAT_SLOTS

    @property
    def visited_blocks(self) -> int:
        """(q block, kv block) tiles computed, of the ``nq * width`` grid
        steps: the rest are skipped as fully masked."""
        i = np.arange(self.nq)[:, None]
        ki = _kv_block_base(i, self, np) + np.arange(self.width)[None, :]
        vis = _block_visited(i, ki, self, np)
        return self.nq * self.width if vis is None else int(vis.sum())


def make_schedule(*, sq: int, skv: int, hd: int, bq: int, bkv: int,
                  groups: int, mode: str, window: int = 0,
                  prefix_len: int = 0, sm_scale: float,
                  hdv: int = 0, stats: str = "full") -> AttnSchedule:
    """Resolve block sizes and the per-q-block kv visitation width.

    For every mode but ``sliding`` each q block walks all kv blocks.
    For ``sliding`` the width is the *block-local fast path*: the maximum
    number of kv blocks any q block's window can touch — O(S * w) grid
    steps instead of O(S^2).  In every mode the block-level ``visited``
    predicate then skips the walk's fully-masked blocks.  ``hdv`` is the
    value head dim (0: the query-key ``hd``); ``stats`` which statistics
    each tile computes (``"range"``: min and max only).
    """
    if mode not in MASK_MODES:
        raise ValueError(f"unknown mask mode {mode!r}; expected {MASK_MODES}")
    if stats not in STAT_MODES:
        raise ValueError(f"unknown stats {stats!r}; expected {STAT_MODES}")
    if mode == "sliding" and window <= 0:
        raise ValueError("sliding mode requires window > 0")
    bq = max(1, min(int(bq), sq))
    bkv = max(1, min(int(bkv), skv))
    # int32 exactness headroom: |rp| <= 255, |v| <= 128 -> the PV int32
    # accumulator stays below 2^24 (exact through the fp32 cast) for
    # bkv <= 512; same bound for the QK^T accumulator over hd.
    hdv = hdv or hd
    if max(hd, hdv, bkv) > 512:
        raise ValueError(f"head dims/bkv must be <= 512 (got {hd}, {hdv}, "
                         f"{bkv})")
    nq = -(-sq // bq)
    nkv = -(-skv // bkv)
    if mode == "sliding":
        width = 1
        for i in range(nq):
            hi = min((i * bq + bq - 1) // bkv, nkv - 1)
            lo = max(0, i * bq - window + 1) // bkv
            width = max(width, hi - lo + 1)
        width = min(width, nkv)
    else:
        width = nkv
    return AttnSchedule(sq=sq, skv=skv, hd=hd, hdv=hdv, bq=bq, bkv=bkv,
                        groups=groups,
                        mode=mode, window=int(window), prefix_len=int(prefix_len),
                        sm_scale=float(sm_scale), width=width, stats=stats)


def _kv_block_span(i, sched: AttnSchedule, xp=jnp):
    """``(first, last)`` kv block holding an attended pair for some row of q
    block ``i``, or None where every block may (cross, bidir).

    The blocks in between all hold one, so the span is the exact set of
    visited blocks.  Traced-int arithmetic (``xp=jnp``: also used inside
    BlockSpec index maps) or static (``xp=numpy``).
    """
    S = sched
    if S.mode in ("cross", "bidir"):
        return None
    last = (i * S.bq + S.bq - 1) // S.bkv         # causal half
    first = i * 0
    if S.mode == "prefix":
        last = xp.maximum(last, (S.prefix_len - 1) // S.bkv)
    elif S.mode == "sliding":                     # window half
        first = xp.maximum(i * S.bq - S.window + 1, 0) // S.bkv
    return first, xp.minimum(last, S.nkv - 1)


def _kv_block_base(i, sched: AttnSchedule, xp=jnp):
    """First kv block index q block ``i`` walks from."""
    if sched.mode != "sliding" or sched.width >= sched.nkv:
        return i * 0
    hi = _kv_block_span(i, sched, xp)[1]
    return xp.clip(hi - (sched.width - 1), 0, max(sched.nkv - sched.width, 0))


def _kv_block_index(i, t, sched: AttnSchedule):
    """kv block read at step ``t`` of q block ``i``: the walk's block,
    clamped into the visited span so that a skipped step presents the
    block of the nearest visited one and the pipeline issues no DMA."""
    ki = _kv_block_base(i, sched) + t
    span = _kv_block_span(i, sched)
    return ki if span is None else jnp.clip(ki, *span)


def _block_visited(i, ki, sched: AttnSchedule, xp=jnp):
    """Block-level skip predicate (None = statically always visited).

    A skipped block is PROVABLY fully masked for every row of the q
    block, so skipping it is exact: the reference applies the same
    predicate with ``where(visited, new, old)`` on its carries.
    """
    span = _kv_block_span(i, sched, xp)
    if span is None:
        return None
    first, last = span
    return (ki >= first) & (ki <= last)


def _element_mask(q_pos, k_pos, kvlen, sched: AttnSchedule):
    """Boolean attend-mask of every core, with the runtime kv length and
    the static skv bound (kills block-padding / OOB-read garbage)."""
    if sched.mode in ("cross", "bidir"):
        m = jnp.ones(jnp.broadcast_shapes(q_pos.shape, k_pos.shape), bool)
    elif sched.mode == "prefix":
        m = (k_pos <= q_pos) | (k_pos < sched.prefix_len)
    elif sched.mode == "sliding":
        m = (k_pos <= q_pos) & (q_pos - k_pos < sched.window)
    else:  # causal
        m = k_pos <= q_pos
    return m & (k_pos < kvlen) & (k_pos < sched.skv)


# ---------------------------------------------------------------------------
# Arithmetic-order pinning (local replica of cnn.layers.fence/tree_sum —
# kernels must not depend on the CNN model package).
# ---------------------------------------------------------------------------
def _runtime_one(x):
    z = (jnp.where(jnp.isfinite(x), x, jnp.zeros_like(x)) * 0.0)
    return z.astype(jnp.float32) + 1.0


def _fence(v):
    """Multiply by a runtime 1.0: pins a mul->add seam against backend- or
    context-dependent FMA contraction (``fma(t, 1.0, y) == t + y`` exactly,
    so the seam is safe whether or not the fence itself contracts)."""
    one = _runtime_one(v.reshape(-1)[0])
    return v * one.astype(v.dtype)


def _tree_sum_last2(v):
    """Pairwise-halving sum over the last TWO axes — a fixed association
    tree, identical for the kernel's [bq, bkv] tile and the reference's
    [..., bq, bkv] batch, so fp statistics accumulate bit-identically."""
    shp = v.shape
    n = shp[-2] * shp[-1]
    v = v.reshape(shp[:-2] + (n,))
    p = 1
    while p < n:
        p *= 2
    if p != n:
        v = jnp.concatenate(
            [v, jnp.zeros(shp[:-2] + (p - n,), v.dtype)], axis=-1)
    while p > 1:
        p //= 2
        v = v[..., :p] + v[..., p:]
    return v[..., 0]


def _tree_sum_flat(v):
    """Pairwise-halving sum of a 1-D vector (final partials reduction)."""
    return _tree_sum_last2(v.reshape(1, -1))


# ---------------------------------------------------------------------------
# The shared per-block recurrence.  These three functions ARE the parity
# contract: the Pallas kernel body and the order-pinned reference both call
# them (on [bq, bkv] tiles and [..., bq, bkv] batches respectively); only
# the int32 contractions around them differ in operator (dot_general vs
# einsum), and integer accumulation is exact in any association.
# ---------------------------------------------------------------------------
def _scores_to_probs(acc_qk, mask, m_prev, alpha_qk, scale_p, zp_p):
    """int32 QK^T accumulator tile -> quantized probabilities.

    Returns ``(rp, p, p_int, m_new, corr)`` where ``rp`` is the
    zero-point-corrected int32 probability image (masked entries exactly
    0, so block-padding garbage contributes exactly nothing to PV), ``p``
    the fp probabilities the statistics observe and ``p_int`` their grid
    image (dequantized only for the SQNR telemetry).
    """
    s = _fence(alpha_qk * acc_qk.astype(jnp.float32))
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # Masked entries observe (and quantize) an exact 0 — deterministic on
    # both backends even when a row's running max is still NEG_INF (where
    # exp(s - m) would otherwise be 1 for masked garbage).
    p = jnp.where(mask, p, 0.0)
    p_int = jnp.clip(jnp.round(p / scale_p + zp_p),
                     float(P_SPEC.int_min), float(P_SPEC.int_max))
    rp = p_int.astype(jnp.int32) - zp_p.astype(jnp.int32)
    corr = jnp.exp(m_prev - m_new)
    return rp, p, p_int, m_new, corr


def _accumulate(acc_prev, l_prev, corr, acc_pv, rp, alpha_pv, scale_p):
    """Online-softmax carry update with fence-pinned mul->add seams."""
    acc = _fence(acc_prev * corr) + _fence(alpha_pv * acc_pv.astype(jnp.float32))
    lsum = jnp.sum(rp, axis=-1, keepdims=True).astype(jnp.float32)
    l = _fence(l_prev * corr) + _fence(scale_p * lsum)
    return acc, l


# How each (pmin, pmax, clip, n, err, sig) slot folds in one tile's value.
_STAT_FOLD = (jnp.minimum, jnp.maximum, jnp.add, jnp.add, jnp.add, jnp.add)


def _tile_stats(p, p_int, sv, regs, slots):
    """One tile's first ``slots`` of (pmin, pmax, clip, n, err, sig).

    ``regs`` is ``(scale_p, zp_p, p_lo, p_hi)``.  ``sv`` masks to in-bounds
    entries (rows < sq, cols < skv); min/max and the integer-valued
    counters are exact in any association, err/sig use the pinned pairwise
    tree sum.  With ``slots == RANGE_SLOTS`` only the masked min and max
    are traced: the counters, the dequantized image and both tree sums
    exist only when telemetry reads them.
    """
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    pmn = jnp.min(jnp.where(sv, p, big), axis=(-2, -1))
    pmx = jnp.max(jnp.where(sv, p, -big), axis=(-2, -1))
    if slots == RANGE_SLOTS:
        return pmn, pmx
    scale_p, zp_p, p_lo, p_hi = regs
    p_hat = (p_int - zp_p) * scale_p
    clip = jnp.sum(jnp.where(sv & ((p < p_lo) | (p > p_hi)), 1.0, 0.0),
                   axis=(-2, -1))
    cnt = jnp.sum(jnp.where(sv, 1.0, 0.0), axis=(-2, -1))
    err = _tree_sum_last2(_fence(jnp.where(sv, (p - p_hat) ** 2, 0.0)))
    sig = _tree_sum_last2(_fence(jnp.where(sv, p * p, 0.0)))
    return pmn, pmx, clip, cnt, err, sig


def _stats_update(st, p, p_int, sv, regs, slots):
    """Fold one tile into ``[..., STAT_SLOTS]`` partials (the reference's
    layout; the kernel folds the same values into its stats tile).  Slots
    past ``slots`` keep their values."""
    inc = _tile_stats(p, p_int, sv, regs, slots)
    return jnp.stack([fold(st[..., r], v) for r, (fold, v)
                      in enumerate(zip(_STAT_FOLD, inc))]
                     + [st[..., r] for r in range(slots, STAT_SLOTS)],
                     axis=-1)


def _stats_init_values():
    big = jnp.float32(jnp.finfo(jnp.float32).max)
    z = jnp.float32(0.0)
    return big, -big, z, z, z, z


def _stats_init(shape=()):
    z = jnp.zeros(shape, jnp.float32)
    return jnp.stack([z + v for v in _stats_init_values()], axis=-1)


def reduce_pstats(partials: jax.Array):
    """Reduce the ``[BH, nq, 6]`` per-(head, q block) partials to the
    site-level (mn, mx, clip, n, err, sig), the last four 0 under a
    ``"range"`` schedule (XLA drops the outputs no one reads).  SHARED by
    both backends (the partials are bit-identical, so one reduction keeps
    them identical): min/max/counts exact in any association, err/sig
    order-pinned."""
    mn = jnp.min(partials[..., 0])
    mx = jnp.max(partials[..., 1])
    clip = jnp.sum(partials[..., 2])
    n = jnp.sum(partials[..., 3])
    err = _tree_sum_flat(partials[..., 4].reshape(-1))
    sig = _tree_sum_flat(partials[..., 5].reshape(-1))
    return mn, mx, clip, n, err, sig


# ---------------------------------------------------------------------------
# The Pallas kernel.
# Grid: (BH, nq, width) — heads and q blocks parallel, the kv walk is the
# sequential ("arbitrary") dimension carrying the online-softmax scratch.
# ---------------------------------------------------------------------------
def _attn_kernel(q_ref, k_ref, v_ref, ksum_ref, regs_ref, kvlen_ref,
                 out_ref, ml_ref, ps_ref,
                 m_sc, l_sc, acc_sc, st_sc, *, sched: AttnSchedule):
    """``q_ref`` holds q shifted onto the signed grid (``q - 128``, int8) and
    ``ksum_ref`` the per-key sums of k over the head dim, so that both
    contractions run int8 x int8 on the MXU:

        sum_h (q - zp_q) k   = sum_h (q - 128) k + (128 - zp_q) ksum
        sum_j (p - zp_p) v   = sum_j (p - 128) v + (128 - zp_p) sum_j v

    Both identities are exact in int32, so the accumulators equal the
    reference's int32 einsums of the zero-point-corrected images.
    ``st_sc`` is a ``STATS_TILE`` whose row ``r`` holds stat slot ``r``;
    only the schedule's first ``stat_slots`` rows are folded."""
    S = sched
    i = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_sc[...] = jnp.full((S.bq, 1), NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros((S.bq, 1), jnp.float32)
        acc_sc[...] = jnp.zeros((S.bq, S.hdv), jnp.float32)
        st_sc[...] = stats_tile(*_stats_init_values())

    ki = _kv_block_base(i, S) + t

    def _step():
        zp_q = regs_ref[0, 0]
        alpha_qk = regs_ref[0, 1]
        scale_p = regs_ref[0, 2]
        zp_p = regs_ref[0, 3]
        alpha_pv = regs_ref[0, 4]
        p_lo = regs_ref[0, 5]
        p_hi = regs_ref[0, 6]
        kvlen = kvlen_ref[0, 0]

        k = k_ref[0]                                               # [bkv, hd]
        v = v_ref[0]
        acc_qk = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)                      # [bq, bkv]
        acc_qk = acc_qk + (128 - zp_q.astype(jnp.int32)) * ksum_ref[0]

        q_pos = i * S.bq + jax.lax.broadcasted_iota(
            jnp.int32, (S.bq, S.bkv), 0)
        k_pos = ki * S.bkv + jax.lax.broadcasted_iota(
            jnp.int32, (S.bq, S.bkv), 1)
        mask = _element_mask(q_pos, k_pos, kvlen, S)

        rp, p, p_int, m_new, corr = _scores_to_probs(
            acc_qk, mask, m_sc[...], alpha_qk, scale_p, zp_p)
        zp_p_i = zp_p.astype(jnp.int32)
        p_s8 = (rp + (zp_p_i - 128)).astype(jnp.int8)
        vsum = jnp.sum(v.astype(jnp.int32), axis=0, keepdims=True)  # [1, hdv]
        acc_pv = jax.lax.dot_general(
            p_s8, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)                      # [bq, hdv]
        acc_pv = acc_pv + (128 - zp_p_i) * vsum
        acc, l = _accumulate(acc_sc[...], l_sc[...], corr, acc_pv, rp,
                             alpha_pv, scale_p)
        acc_sc[...] = acc
        l_sc[...] = l
        m_sc[...] = m_new

        sv = (q_pos < S.sq) & (k_pos < S.skv)
        inc = _tile_stats(p, p_int, sv, (scale_p, zp_p, p_lo, p_hi),
                          S.stat_slots)
        st = st_sc[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, STATS_TILE, 0)
        new = st
        for r, (fold, val) in enumerate(zip(_STAT_FOLD, inc)):
            new = jnp.where(rows == r, fold(st, val), new)
        st_sc[...] = new

    vis = _block_visited(i, ki, S)
    if vis is None:
        _step()
    else:
        pl.when(vis)(_step)

    @pl.when(t == S.width - 1)
    def _fin():
        l = l_sc[...]
        out_ref[0] = acc_sc[...] / jnp.maximum(l, 1e-30)
        ml_ref[0] = jnp.concatenate([m_sc[...], l], axis=1)
        ps_ref[0, 0] = st_sc[...]


def attention_kernel(q_u8, k_i8, v_i8, regs, kvlen, *, sched: AttnSchedule):
    """Raw pallas_call.  ``q_u8`` uint8 [BH, sq, hd]; ``k_i8`` int8 [ZB,
    skv, hd] and ``v_i8`` int8 [ZB, skv, hdv] (ZB = BH // groups); ``regs``
    fp32 [1, 8]; ``kvlen`` int32 [1, 1].  Returns ``(out [BH, sq, hdv]
    f32, ml [BH, sq, 2] f32,
    pstats [BH, nq, 6] f32)``."""
    S = sched
    bh = q_u8.shape[0]
    g = S.groups
    q_s8 = (q_u8.astype(jnp.int16) - 128).astype(jnp.int8)
    ksum = jnp.sum(k_i8.astype(jnp.int32), axis=-1)[:, None, :]   # [ZB,1,skv]

    def kvmap(b, i, t):
        return (b // g, _kv_block_index(i, t, S), 0)

    def ksum_map(b, i, t):
        return (b // g, 0, _kv_block_index(i, t, S))

    out, ml, ps = pl.pallas_call(
        functools.partial(_attn_kernel, sched=S),
        grid=(bh, S.nq, S.width),
        in_specs=[
            pl.BlockSpec((1, S.bq, S.hd), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, S.bkv, S.hd), kvmap),
            pl.BlockSpec((1, S.bkv, S.hdv), kvmap),
            pl.BlockSpec((1, 1, S.bkv), ksum_map),
            pl.BlockSpec((1, 8), lambda b, i, t: (0, 0)),
            pl.BlockSpec((1, 1), lambda b, i, t: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S.bq, S.hdv), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, S.bq, 2), lambda b, i, t: (b, i, 0)),
            pl.BlockSpec((1, 1) + STATS_TILE, lambda b, i, t: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S.sq, S.hdv), jnp.float32),
            jax.ShapeDtypeStruct((bh, S.sq, 2), jnp.float32),
            jax.ShapeDtypeStruct((bh, S.nq) + STATS_TILE, jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((S.bq, 1), jnp.float32),
            pltpu.VMEM((S.bq, 1), jnp.float32),
            pltpu.VMEM((S.bq, S.hdv), jnp.float32),
            pltpu.VMEM(STATS_TILE, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=platform.interpret_mode(),
    )(q_s8, k_i8, v_i8, ksum, regs, kvlen)
    return out, ml, stats_values(ps, STAT_SLOTS)


# ---------------------------------------------------------------------------
# The order-pinned reference (the ``simulated`` backend's attention core).
# Replays the kernel's exact block schedule; carries update through
# ``where(visited, new, old)`` — value-identical to the kernel's
# ``pl.when`` skip.
# ---------------------------------------------------------------------------
def _pad_axis(x, size, axis):
    cur = x.shape[axis]
    if cur == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - cur)
    return jnp.pad(x, pads)


def attention_core_reference(q_u8, k_i8, v_i8, regs, kvlen, *,
                             sched: AttnSchedule):
    """Pure-jnp order-pinned replay of :func:`attention_kernel`.

    Same shapes/returns as the kernel.  All block-padding values are
    zero-padded here vs clamped block reads in interpret mode — every
    such value is provably masked to an exact 0 contribution before use,
    so the difference is unobservable.
    """
    S = sched
    bh = q_u8.shape[0]
    zb = bh // S.groups
    qz = _pad_axis(q_u8, S.nq * S.bq, 1).reshape(
        zb, S.groups, S.nq, S.bq, S.hd)
    kz = _pad_axis(k_i8, S.nkv * S.bkv, 1).reshape(zb, S.nkv, S.bkv, S.hd)
    vz = _pad_axis(v_i8, S.nkv * S.bkv, 1).reshape(zb, S.nkv, S.bkv, S.hdv)
    zp_q, alpha_qk, scale_p, zp_p, alpha_pv, p_lo, p_hi = (
        regs[0, 0], regs[0, 1], regs[0, 2], regs[0, 3], regs[0, 4],
        regs[0, 5], regs[0, 6])
    kvl = kvlen[0, 0]

    def q_body(i):
        qb = jax.lax.dynamic_index_in_dim(qz, i, 2, keepdims=False)
        rq = qb.astype(jnp.int32) - zp_q.astype(jnp.int32)  # [ZB, G, bq, hd]
        base = _kv_block_base(i, S)

        def kv_body(carry, t):
            m, l, acc, st = carry
            ki = base + t
            kb = jax.lax.dynamic_index_in_dim(kz, ki, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vz, ki, 1, keepdims=False)
            rk = kb.astype(jnp.int32)                       # [ZB, bkv, hd]
            rv = vb.astype(jnp.int32)
            acc_qk = jnp.einsum("zgqh,zkh->zgqk", rq, rk,
                                preferred_element_type=jnp.int32)

            q_pos = i * S.bq + jax.lax.broadcasted_iota(
                jnp.int32, (S.bq, S.bkv), 0)
            k_pos = ki * S.bkv + jax.lax.broadcasted_iota(
                jnp.int32, (S.bq, S.bkv), 1)
            mask = _element_mask(q_pos, k_pos, kvl, S)[None, None]

            rp, p, p_int, m_new, corr = _scores_to_probs(
                acc_qk, mask, m, alpha_qk, scale_p, zp_p)
            acc_pv = jnp.einsum("zgqk,zkh->zgqh", rp, rv,
                                preferred_element_type=jnp.int32)
            acc_n, l_n = _accumulate(acc, l, corr, acc_pv, rp,
                                     alpha_pv, scale_p)
            sv = ((q_pos < S.sq) & (k_pos < S.skv))[None, None]
            st_n = _stats_update(st, p, p_int, sv,
                                 (scale_p, zp_p, p_lo, p_hi), S.stat_slots)

            vis = _block_visited(i, ki, S)
            if vis is not None:
                m_new = jnp.where(vis, m_new, m)
                l_n = jnp.where(vis, l_n, l)
                acc_n = jnp.where(vis, acc_n, acc)
                st_n = jnp.where(vis, st_n, st)
            return (m_new, l_n, acc_n, st_n), None

        m0 = jnp.full((zb, S.groups, S.bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((zb, S.groups, S.bq, 1), jnp.float32)
        a0 = jnp.zeros((zb, S.groups, S.bq, S.hdv), jnp.float32)
        st0 = _stats_init((zb, S.groups))
        (m, l, acc, st), _ = jax.lax.scan(kv_body, (m0, l0, a0, st0),
                                          jnp.arange(S.width))
        out_i = acc / jnp.maximum(l, 1e-30)
        ml_i = jnp.concatenate([m, l], axis=-1)
        return out_i, ml_i, st

    outs, mls, sts = jax.lax.map(q_body, jnp.arange(S.nq))
    # [nq, ZB, G, bq, ...] -> kernel element order [BH, sq, ...]
    out = jnp.transpose(outs, (1, 2, 0, 3, 4)).reshape(
        bh, S.nq * S.bq, S.hdv)[:, :S.sq]
    ml = jnp.transpose(mls, (1, 2, 0, 3, 4)).reshape(
        bh, S.nq * S.bq, 2)[:, :S.sq]
    pstats = jnp.transpose(sts, (1, 2, 0, 3)).reshape(bh, S.nq, STAT_SLOTS)
    return out, ml, pstats


# ---------------------------------------------------------------------------
# The fp core: policies without static 8-bit ranges (dynamic, disabled,
# other widths) have no int8 core, and run the same block walk in fp32.
# ---------------------------------------------------------------------------
def attention_core_fp(q, k, v, kvlen, *, sched: AttnSchedule):
    """fp32 online-softmax attention on the schedule's block walk.

    ``q [BH, sq, hd]``, ``k [ZB, skv, hd]`` and ``v [ZB, skv, hdv]`` in any
    float dtype, ``kvlen`` int32 [1, 1]; returns ``out [BH, sq, hdv]`` f32.
    Each q block walks the ``sched.width`` kv blocks from
    ``_kv_block_base`` (O(S * w) under a sliding window) and computes only
    those ``_block_visited`` keeps.  Masked scores take NEG_INF and an
    exact 0 probability, and the denominator is floored at 1e-30, so a row
    with no attended key returns 0.  JAX differentiates it as written.
    """
    S = sched
    bh = q.shape[0]
    zb = bh // S.groups
    qz = _pad_axis(q.astype(jnp.float32) * S.sm_scale, S.nq * S.bq,
                   1).reshape(zb, S.groups, S.nq, S.bq, S.hd)
    kz = _pad_axis(k.astype(jnp.float32), S.nkv * S.bkv, 1).reshape(
        zb, S.nkv, S.bkv, S.hd)
    vz = _pad_axis(v.astype(jnp.float32), S.nkv * S.bkv, 1).reshape(
        zb, S.nkv, S.bkv, S.hdv)
    kvl = kvlen[0, 0]

    def q_body(i):
        qb = jax.lax.dynamic_index_in_dim(qz, i, 2, keepdims=False)
        base = _kv_block_base(i, S)

        def block(carry, ki):
            m, l, acc = carry
            kb = jax.lax.dynamic_index_in_dim(kz, ki, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vz, ki, 1, keepdims=False)
            q_pos = i * S.bq + jax.lax.broadcasted_iota(
                jnp.int32, (S.bq, S.bkv), 0)
            k_pos = ki * S.bkv + jax.lax.broadcasted_iota(
                jnp.int32, (S.bq, S.bkv), 1)
            mask = _element_mask(q_pos, k_pos, kvl, S)
            s = jnp.where(mask, jnp.einsum("zgqh,zkh->zgqk", qb, kb), NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr + jnp.einsum("zgqk,zkh->zgqh", p, vb)
            return m_new, l, acc

        def kv_body(carry, t):
            ki = base + t
            vis = _block_visited(i, ki, S)
            if vis is None:
                return block(carry, ki), None
            return jax.lax.cond(vis, block, lambda c, _: c, carry, ki), None

        m0 = jnp.full((zb, S.groups, S.bq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((zb, S.groups, S.bq, 1), jnp.float32)
        a0 = jnp.zeros((zb, S.groups, S.bq, S.hdv), jnp.float32)
        (_, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                      jnp.arange(S.width))
        return acc / jnp.maximum(l, 1e-30)

    outs = jax.lax.map(q_body, jnp.arange(S.nq))     # [nq, ZB, G, bq, hdv]
    return jnp.transpose(outs, (1, 2, 0, 3, 4)).reshape(
        bh, S.nq * S.bq, S.hdv)[:, :S.sq]


# ---------------------------------------------------------------------------
# Recompute-based backward, SHARED by both backends (the qconv precedent:
# one deterministic jnp formulation of the cotangents, fed bit-identical
# residuals, keeps full-step parameter parity across backends).
#
# Semantics: clipped-STE through the q/k/v quantizers is applied by the
# enclosing site quantizers; inside the core the p quantization and the
# per-block softmax maxima are treated as straight-through constants, so
# the cotangents are the standard flash-attention backward evaluated on
# p_fin = exp(s - m_final) with s recomputed through the SAME int8 QK^T
# contraction as the forward.
# ---------------------------------------------------------------------------
def attention_core_backward(qh, kh, vh, q_u8, k_i8, v_i8, regs, kvlen,
                            out, ml, g_out, *, sched: AttnSchedule):
    """Returns ``(dq [BH, sq, hd], dk [ZB, skv, hd], dv [ZB, skv, hdv])``
    fp32 cotangents w.r.t. the on-grid (dequantized) q/k/v tensors."""
    S = sched
    bh = q_u8.shape[0]
    zb = bh // S.groups
    sqp, skp = S.nq * S.bq, S.nkv * S.bkv

    def qsplit(x, d):
        return _pad_axis(x, sqp, 1).reshape(zb, S.groups, S.nq, S.bq, d)

    def ksplit(x, d):
        return _pad_axis(x, skp, 1).reshape(zb, S.nkv, S.bkv, d)

    gf = g_out.astype(jnp.float32)
    d_row = jnp.einsum("bsh,bsh->bs", gf, out.astype(jnp.float32))
    qz = qsplit(q_u8, S.hd)
    qhz = qsplit(qh.astype(jnp.float32), S.hd)
    gz = qsplit(gf, S.hdv)
    mz = qsplit(ml[..., 0:1], 1)[..., 0]                   # [ZB,G,nq,bq]
    lz = qsplit(ml[..., 1:2], 1)[..., 0]
    dz = qsplit(d_row[..., None], 1)[..., 0]
    kz = ksplit(k_i8, S.hd)
    khz = ksplit(kh.astype(jnp.float32), S.hd)
    vhz = ksplit(vh.astype(jnp.float32), S.hdv)
    zp_q, alpha_qk = regs[0, 0], regs[0, 1]
    kvl = kvlen[0, 0]
    sm = jnp.float32(S.sm_scale)

    def outer(carry, i):
        dk_acc, dv_acc = carry                     # [ZB, nkv, bkv, hd / hdv]
        rq = (jax.lax.dynamic_index_in_dim(qz, i, 2, False).astype(jnp.int32)
              - zp_q.astype(jnp.int32))
        qh_i = jax.lax.dynamic_index_in_dim(qhz, i, 2, False)
        g_i = jax.lax.dynamic_index_in_dim(gz, i, 2, False)
        m_i = jax.lax.dynamic_index_in_dim(mz, i, 2, False)[..., None]
        l_i = jax.lax.dynamic_index_in_dim(lz, i, 2, False)[..., None]
        d_i = jax.lax.dynamic_index_in_dim(dz, i, 2, False)[..., None]

        def inner(icarry, j):
            dq_i, dk_acc, dv_acc = icarry
            rk = jax.lax.dynamic_index_in_dim(kz, j, 1, False).astype(jnp.int32)
            kh_j = jax.lax.dynamic_index_in_dim(khz, j, 1, False)
            vh_j = jax.lax.dynamic_index_in_dim(vhz, j, 1, False)
            acc_qk = jnp.einsum("zgqh,zkh->zgqk", rq, rk,
                                preferred_element_type=jnp.int32)
            s = _fence(alpha_qk * acc_qk.astype(jnp.float32))
            q_pos = i * S.bq + jax.lax.broadcasted_iota(
                jnp.int32, (S.bq, S.bkv), 0)
            k_pos = j * S.bkv + jax.lax.broadcasted_iota(
                jnp.int32, (S.bq, S.bkv), 1)
            # Padded q rows (>= sq) carry zero-padded (m, l) residuals and
            # garbage scores; mask them out or r = p / max(l, eps) overflows
            # and 0-cotangent * inf turns into NaN in dk/dv.
            mask = (_element_mask(q_pos, k_pos, kvl, S)
                    & (q_pos < S.sq))[None, None]
            p = jnp.where(mask, jnp.exp(s - m_i), 0.0)
            r = p / jnp.maximum(l_i, 1e-30)                 # softmax probs
            d_ov = jnp.einsum("zgqh,zkh->zgqk", g_i, vh_j)
            ds = (r * (d_ov - d_i)) * sm
            dq_i = dq_i + jnp.einsum("zgqk,zkh->zgqh", ds, kh_j)
            dk_j = jnp.einsum("zgqk,zgqh->zkh", ds, qh_i)
            dv_j = jnp.einsum("zgqk,zgqh->zkh", r, g_i)
            dk_acc = dk_acc.at[:, j].add(dk_j)
            dv_acc = dv_acc.at[:, j].add(dv_j)
            return (dq_i, dk_acc, dv_acc), None

        dq0 = jnp.zeros((zb, S.groups, S.bq, S.hd), jnp.float32)
        (dq_i, dk_acc, dv_acc), _ = jax.lax.scan(
            inner, (dq0, dk_acc, dv_acc), jnp.arange(S.nkv))
        return (dk_acc, dv_acc), dq_i

    dk0 = jnp.zeros((zb, S.nkv, S.bkv, S.hd), jnp.float32)
    dv0 = jnp.zeros((zb, S.nkv, S.bkv, S.hdv), jnp.float32)
    (dk_acc, dv_acc), dqs = jax.lax.scan(outer, (dk0, dv0),
                                         jnp.arange(S.nq))
    dq = jnp.transpose(dqs, (1, 2, 0, 3, 4)).reshape(
        bh, sqp, S.hd)[:, :S.sq]
    dk = dk_acc.reshape(zb, skp, S.hd)[:, :S.skv]
    dv = dv_acc.reshape(zb, skp, S.hdv)[:, :S.skv]
    return dq, dk, dv
