"""Vectorized CEP: batched NFA state transitions for ALL keys at once.

The interpreted matcher (``cep/operator.py``, ``NFA.advance``) walks one
event x one partial match at a time in Python — the last hot-path workload
still paying per-record host work (ROADMAP item 4).  This module compiles a
``Pattern`` into a dense :class:`TransitionTable` and advances **every
key's partial matches in one batched dispatch per event step**: the active
partials of all keys live in fixed-shape arrays ``[K, M]`` (stage index,
loop count, first timestamp, a bounded event-pointer ring), the per-stage
condition bits that ``process_batch`` already evaluates vectorized become
the kernel's input planes, and the NFA edges (take / ignore / die /
optional-forward / negation) become masked gather/scatter updates.
``within()`` expiry and the after-match skip barrier apply as vectorized
masks; host code touches only *completed* matches.

Equivalence contract: for every **eligible** pattern (see
:func:`classify_pattern`) the kernel produces bit-identical results to the
interpreted NFA — same matches, same order, same partial-match lists after
every event.  The candidate layout mirrors ``NFA.advance``'s generation
order exactly (per partial: take-stay, take-advance, keep; the fresh start
partial appended last), candidate dedup mirrors the ``seen`` set (exact
comparison, hash-prefiltered), and completed matches bypass dedup just as
``add()`` does.

Ineligible shapes — ``followedByAny`` (non-deterministic branch
explosion), ``greedy()`` loops, and drain-time/``PREV`` conditions
(MATCH_RECOGNIZE) — fall back to the interpreted NFA, decided once at plan
time.

Two kernel backends share one generic step (``xp`` = numpy or
``jax.numpy``):

- ``numpy``: the host-vectorized path (one pass of array ops per event
  step across all keys); the winner on CPU backends.
- ``jit``: the same step under ``jax.jit`` (int64 planes via scoped
  ``enable_x64``), one dispatched step per event position — the
  accelerator path.  Candidate dedup inside the jit is hash-prefiltered
  only; any hash collision raises a flag and the step replays on the
  numpy path with exact comparison, so bit-identity never rests on a
  hash.

:func:`calibrated_vectorized_cep` is the measured engine A/B behind
``CepOperator(vectorized="auto")``: each engine runs one synthetic batch
once per process, and the faster one is kept.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from flink_tpu.core.batch import LONG_MIN
from flink_tpu.cep.pattern import AfterMatchSkipStrategy, Pattern

#: event pointers pack (stage << PACK_SHIFT) | event_id into one int64
PACK_SHIFT = 48
_PACK_MASK = (1 << PACK_SHIFT) - 1

#: sentinel for "no within window"
_NO_WITHIN = -1

_ENV_ENGINE = "FLINK_TPU_CEP_VECTORIZED"
_ENV_KERNEL = "FLINK_TPU_CEP_KERNEL"

#: rolling-hash multiplier for the per-partial event-list hash (int32 wrap)
_HASH_MUL = np.int32(1000003)


# ---------------------------------------------------------------------------
# plan-time classifier + transition table
# ---------------------------------------------------------------------------

def classify_pattern(pattern: Pattern) -> Tuple[bool, List[str]]:
    """Is this pattern eligible for the vectorized kernel?

    First cut keeps the branching bounded (<= 3 successor candidates per
    partial per event, mirroring ``NFA.advance``'s edge set):

    - ``followedByAny`` (``relaxed_any``) multiplies ignore edges for
      *matching* events — unbounded combination explosion.
    - ``greedy()`` loops couple a partial's fate to its *sibling's* bits
      (``greedy_from`` suppression), an extra cross-partial plane.

    Everything else — strict/relaxed contiguity, ``notNext`` /
    ``notFollowedBy`` (incl. trailing under ``within``), ``times`` /
    ``oneOrMore`` / ``optional``, ``until``, both after-match skip
    strategies — lowers exactly.  Returns ``(eligible, reasons)``.
    """
    reasons = []
    for s in pattern.stages:
        if s.contiguity == "relaxed_any":
            reasons.append(f"stage {s.name!r}: followedByAny (relaxed_any) "
                           f"contiguity")
        if s.greedy:
            reasons.append(f"stage {s.name!r}: greedy loop")
    return (not reasons), reasons


@dataclass(frozen=True)
class TransitionTable:
    """A ``Pattern`` compiled to dense per-stage planes (all numpy; the
    jit kernel closes over them as constants)."""

    n_stages: int
    strict: np.ndarray      # bool[S]: 'next' contiguity
    negated: np.ndarray     # bool[S]
    optional: np.ndarray    # bool[S]
    tmin: np.ndarray        # int64[S] quantifier lower bound
    tmax: np.ndarray        # int64[S] upper bound (LONG_MAX-ish = unbounded)
    within: int             # ms, or _NO_WITHIN
    skip_past: bool         # SKIP_PAST_LAST_EVENT
    trailing_negation: bool
    has_until: bool


def compile_pattern(pattern: Pattern) -> TransitionTable:
    stages = pattern.stages
    S = len(stages)
    unbounded = np.int64(2 ** 62)
    last = stages[-1]
    return TransitionTable(
        n_stages=S,
        strict=np.asarray([s.contiguity == "strict" for s in stages], bool),
        negated=np.asarray([s.negated for s in stages], bool),
        optional=np.asarray([s.optional for s in stages], bool),
        tmin=np.asarray([s.times_min for s in stages], np.int64),
        tmax=np.asarray([s.times_max if s.times_max is not None
                         else unbounded for s in stages], np.int64),
        within=(pattern.within_ms if pattern.within_ms is not None
                else _NO_WITHIN),
        skip_past=(pattern.skip_strategy
                   == AfterMatchSkipStrategy.SKIP_PAST_LAST_EVENT),
        trailing_negation=(last.negated and last.contiguity != "strict"
                           and pattern.within_ms is not None),
        has_until=any(s.until is not None for s in stages),
    )


# ---------------------------------------------------------------------------
# packing helpers
# ---------------------------------------------------------------------------

def pack_event(stage: int, event_id: int) -> int:
    return (int(stage) << PACK_SHIFT) | int(event_id)


def unpack_events(row: np.ndarray) -> Tuple[Tuple[int, int], ...]:
    r = np.asarray(row, np.int64)
    return tuple((int(p) >> PACK_SHIFT, int(p) & _PACK_MASK) for p in r)


def _fold32(packed):
    """int64 packed pointer -> int32 hash lane (both words folded)."""
    p = packed.astype(np.int64) if hasattr(packed, "astype") else packed
    lo = (p & np.int64(0xFFFFFFFF)).astype(np.int32)
    hi = (p >> np.int64(32)).astype(np.int32)
    return lo ^ (hi * np.int32(31))


def event_list_hash(packed_row) -> np.int32:
    """Rolling int32 hash of an event list — MUST match the kernel's
    incremental update (``h' = h * _HASH_MUL + fold32(packed)``).  Runs on
    1-element arrays so int32 wraparound stays silent (scalar overflow
    warns under ``-W error``)."""
    r = np.asarray(packed_row, np.int64).reshape(-1)
    folded = _fold32(r)
    h = np.zeros(1, np.int32)
    for i in range(r.size):
        h = h * _HASH_MUL + folded[i:i + 1]
    return np.int32(h[0])


# ---------------------------------------------------------------------------
# the generic per-event transition step (xp = numpy | jax.numpy)
# ---------------------------------------------------------------------------

def _stable_argsort(xp, a, axis):
    if xp is np:
        return np.argsort(a, axis=axis, kind="stable")
    return xp.argsort(a, axis=axis)      # jnp sorts are stable by default


def _gather_stage(xp, plane, stage, S):
    """plane[Ka, S] gathered at stage[Ka, M] -> [Ka, M] (clipped gather —
    out-of-range stages are masked off by callers)."""
    idx = xp.clip(stage, 0, S - 1)
    return xp.take_along_axis(plane, idx, axis=1)


def _candidates(xp, tab: TransitionTable, block, inputs):
    """One NFA event step for a block of keys: build the candidate arrays.

    ``block``: (st, cnt, fst, eln, ev, evh, nlv, skip) — [Ka, M] planes
    (+ ev [Ka, M, E], nlv/skip [Ka]).  ``inputs``: (active, ets, eid,
    bits, ubits) with bits/ubits [Ka, S].

    Returns candidate planes laid out ``[Ka, C=3M+1]`` in the interpreted
    generation order (per partial m: 3m+0 take-stay, 3m+1 take-advance,
    3m+2 keep; slot 3M = the fresh start partial appended last), plus
    ``stepping`` and the E-overflow flag.
    """
    st, cnt, fst, eln, ev, evh, nlv, skip = block
    active, ets, eid, bits, ubits = inputs
    Ka, M = st.shape
    E = ev.shape[2]
    S = tab.n_stages

    m_idx = xp.arange(M, dtype=np.int32)[None, :]
    live = m_idx < nlv[:, None]
    stepping = active & (ets > skip)                      # skip barrier
    act = stepping[:, None] & live
    ts_b = ets[:, None]

    # within-window expiry (guard LONG_MIN before subtracting)
    if tab.within != _NO_WITHIN:
        safe_fst = xp.where(fst == LONG_MIN, ts_b, fst)
        expired = (fst != LONG_MIN) & (ts_b - safe_fst > tab.within)
    else:
        expired = xp.zeros_like(live)
    alive = act & ~expired

    stage_c = xp.clip(st, 0, S - 1)
    neg_plane = xp.asarray(tab.negated)
    strict_plane = xp.asarray(tab.strict)
    opt_plane = xp.asarray(tab.optional)
    tmin_plane = xp.asarray(tab.tmin)
    tmax_plane = xp.asarray(tab.tmax)

    neg = neg_plane[stage_c] & alive
    strictneg = neg & strict_plane[stage_c]
    relaxneg = neg & ~strict_plane[stage_c]
    normal = alive & ~neg

    b_at = _gather_stage(xp, bits, st, S) & alive
    u_at = _gather_stage(xp, ubits, st, S) & alive

    neg_dead = neg & b_at                   # forbidden event: partial dies
    norm_until_dead = normal & u_at & (cnt > 0)
    normal_f = normal & ~norm_until_dead
    strictneg_f = strictneg & ~neg_dead
    relaxneg_f = relaxneg & ~neg_dead

    # ---- feed(): chain walk through optional stages to the take stage j.
    # own = the stage whose until() can close the loop (the partial's own
    # stage for normal partials; the advanced stage for notNext; never for
    # notFollowedBy — feed there starts past the partial's own stage).
    cs = xp.where(neg, st + 1, st)
    own = xp.where(relaxneg, xp.full_like(st, -1),
                   xp.where(strictneg, st + 1, st))
    took_nothing0 = xp.where(neg, xp.ones_like(live), cnt == 0)

    sn_complete = strictneg_f & (cs >= S)   # notNext ends the pattern
    feeding = (normal_f | strictneg_f | relaxneg_f) & (cs < S)

    jj = xp.clip(cs, 0, S - 1)
    remaining = feeding
    matched = xp.zeros_like(live)
    take_j = jj
    for _ in range(S):
        bj = xp.take_along_axis(bits, jj, axis=1)
        uj = xp.take_along_axis(ubits, jj, axis=1)
        negj = neg_plane[jj]
        ublock = (jj == own) & uj
        take_here = remaining & bj & ~negj & ~ublock
        tn = xp.where(jj == cs, took_nothing0, xp.ones_like(live))
        fwd = (remaining & ~take_here & ~negj & ~(bj & ublock)
               & ~bj & opt_plane[jj] & tn & (jj + 1 < S))
        take_j = xp.where(take_here, jj, take_j)
        matched = matched | take_here
        remaining = fwd
        jj = xp.where(fwd, jj + 1, jj)

    # ---- take candidates (stay in loop / advance pointer)
    cnt_at_j = xp.where((take_j == own) & ~neg, cnt, xp.zeros_like(cnt))
    newc = cnt_at_j + 1
    first_f = xp.where(fst == LONG_MIN, ts_b, fst)
    tmax_j = tmax_plane[take_j]
    tmin_j = tmin_plane[take_j]
    stay_ok = matched & (newc.astype(np.int64) < tmax_j)
    adv_ok = matched & (newc.astype(np.int64) >= tmin_j)
    adv_stage = take_j + 1
    adv_is_match = adv_ok & (adv_stage >= S)

    packed = ((take_j.astype(np.int64) << PACK_SHIFT)
              | eid[:, None].astype(np.int64))
    e_idx = xp.arange(E, dtype=np.int32)[None, None, :]
    ev_app = xp.where(e_idx == eln[:, :, None], packed[:, :, None], ev)
    evh_app = (evh * _HASH_MUL + _fold32(packed)).astype(np.int32)
    # E overflow: a take with a full ring cannot record its pointer
    overflow_e = xp.any((stay_ok | adv_ok) & (eln >= E))

    # ---- keep candidates
    keep_normal = normal_f & (((st == 0) & (cnt == 0))
                              | (~matched & ~strict_plane[stage_c]))
    nxt_c = xp.clip(cs, 0, S - 1)
    keep_sn = (strictneg_f & (cs < S) & ~matched & ~strict_plane[nxt_c])
    keep_rn = relaxneg_f & ~matched & ((cs >= S) | ~strict_plane[nxt_c])

    keep_valid = keep_normal | keep_rn | keep_sn | sn_complete
    # keep content: pm unchanged, EXCEPT notNext which keeps the advanced
    # partial (stage+1, count 0, first filled)
    sn_like = strictneg_f & (keep_sn | sn_complete)
    keep_st = xp.where(sn_like, cs, st)
    keep_cnt = xp.where(sn_like, xp.zeros_like(cnt), cnt)
    keep_fst = xp.where(sn_like, first_f, fst)

    # ---- assemble [Ka, C] candidate planes (C = 3M + 1)
    def lay(a0, a1, a2, start_val, dtype):
        tri = xp.stack([a0, a1, a2], axis=2).reshape(Ka, 3 * M)
        startc = xp.full((Ka, 1), start_val, dtype)
        return xp.concatenate([tri, startc], axis=1)

    zil = xp.zeros_like
    c_st = lay(take_j, adv_stage, keep_st, np.int32(0), np.int32)
    c_cnt = lay(newc, zil(newc), keep_cnt, np.int32(0), np.int32)
    c_fst = lay(first_f, first_f, keep_fst, np.int64(LONG_MIN), np.int64)
    c_eln = lay(eln + 1, eln + 1, eln, np.int32(0), np.int32)
    c_evh = lay(evh_app, evh_app, evh, np.int32(0), np.int32)
    c_valid = lay(stay_ok, adv_ok, keep_valid, False, bool)
    c_match = lay(zil(stay_ok), adv_is_match, sn_complete, False, bool)
    ev_tri = xp.stack([ev_app, ev_app, ev], axis=2).reshape(Ka, 3 * M, E)
    c_ev = xp.concatenate(
        [ev_tri, xp.zeros((Ka, 1, E), np.int64)], axis=1)

    # the fresh start partial is appended only when no surviving candidate
    # already sits at (stage 0, count 0) — interpreted NFA end-of-advance
    has_start = xp.any(c_valid[:, :3 * M] & ~c_match[:, :3 * M]
                       & (c_st[:, :3 * M] == 0) & (c_cnt[:, :3 * M] == 0),
                       axis=1)
    start_col_valid = stepping & ~has_start
    c_valid = xp.concatenate(
        [c_valid[:, :3 * M], start_col_valid[:, None]], axis=1)

    cand = dict(st=c_st, cnt=c_cnt, fst=c_fst, eln=c_eln, ev=c_ev,
                evh=c_evh, valid=c_valid, ismatch=c_match)
    return cand, stepping, overflow_e


def _cand_hash(xp, cand):
    """int32 identity hash per candidate: (stage, count, elen, event-list
    rolling hash) — the dedup prefilter."""
    h = (cand["st"].astype(np.int32) * np.int32(31)
         + cand["cnt"].astype(np.int32))
    h = h * _HASH_MUL + cand["eln"].astype(np.int32)
    return (h * _HASH_MUL + cand["evh"]).astype(np.int32)


def _dup_prefilter(xp, cand):
    """dup[k, c] = an EARLIER valid non-match candidate has the same hash —
    the vectorized ``seen``-set prefilter (exact verification is the
    caller's job on rows where this fires)."""
    h = _cand_hash(xp, cand)
    eligible = cand["valid"] & ~cand["ismatch"]
    C = h.shape[1]
    eq = (h[:, None, :] == h[:, :, None])          # [Ka, C(earlier), C]
    tri = xp.asarray(np.tril(np.ones((C, C), bool), -1)).T  # earlier < c
    hit = eq & tri[None, :, :] & eligible[:, :, None] & eligible[:, None, :]
    return xp.any(hit, axis=1)


def _dup_candidate_rows(cand) -> np.ndarray:
    """Numpy fast path: rows that MIGHT contain a duplicate candidate —
    detected by sorting each row's (valid, non-match) candidate hashes and
    looking for adjacent equals (O(C log C) instead of the [C, C] pairwise
    plane).  Invalid slots get per-position sentinels above the int32 hash
    range so they can never create a false adjacency."""
    h = _cand_hash(np, cand).astype(np.int64)
    eligible = cand["valid"] & ~cand["ismatch"]
    C = h.shape[1]
    sentinel = (np.arange(C, dtype=np.int64) + (np.int64(1) << 33))[None, :]
    hm = np.where(eligible, h, sentinel)
    hs = np.sort(hm, axis=1)
    return np.flatnonzero((hs[:, 1:] == hs[:, :-1]).any(axis=1))


def _finalize(xp, M_out: int, cand, dup, block, stepping, ets,
              skip_past: bool):
    """Compact surviving candidates (valid, non-match, non-dup) into the
    first ``M_out`` slots in candidate order; apply the after-match skip
    reset; keep non-stepping keys' rows untouched.  Returns the new block
    plus the M-overflow flag."""
    st, cnt, fst, eln, ev, evh, nlv, skip = block
    Ka, M = st.shape
    E = ev.shape[2]
    C = cand["st"].shape[1]

    keep = cand["valid"] & ~cand["ismatch"] & ~dup
    ncand = keep.sum(axis=1).astype(np.int32)
    overflow_m = xp.max(ncand, initial=0) if xp is np else xp.max(
        xp.concatenate([ncand, xp.zeros(1, np.int32)]))
    overflow_m = overflow_m > M_out

    # stable compaction: argsort(~keep) puts kept candidates first, in
    # order.  M_out may exceed C (a pow2 growth overshooting 3M+1 when a
    # step nearly triples the partial set): gather the min(M_out, C)
    # candidate columns that exist, then pad to M_out — the dead-slot
    # masking below restores the pristine pattern on the padding.
    W = min(M_out, C)
    order = _stable_argsort(xp, ~keep, axis=1)[:, :W]
    take2 = lambda a: xp.take_along_axis(a, order, axis=1)  # noqa: E731

    def padw(a, fill):
        if W >= M_out:
            return a
        return xp.concatenate(
            [a, xp.full((Ka, M_out - W) + a.shape[2:], fill, a.dtype)],
            axis=1)

    n_st = padw(take2(cand["st"]), np.int32(0))
    n_cnt = padw(take2(cand["cnt"]), np.int32(0))
    n_fst = padw(take2(cand["fst"]), np.int64(LONG_MIN))
    n_eln = padw(take2(cand["eln"]), np.int32(0))
    n_evh = padw(take2(cand["evh"]), np.int32(0))
    n_ev = padw(xp.take_along_axis(cand["ev"], order[:, :, None], axis=1),
                np.int64(0))

    # after-match skip: a completing match resets the key to one fresh
    # start partial and raises the skip barrier to the match event's ts
    any_match = xp.any(cand["ismatch"] & cand["valid"], axis=1) & stepping
    if skip_past:
        rst = any_match[:, None]
        n_st = xp.where(rst, xp.zeros_like(n_st), n_st)
        n_cnt = xp.where(rst, xp.zeros_like(n_cnt), n_cnt)
        n_fst = xp.where(rst, xp.full_like(n_fst, LONG_MIN), n_fst)
        n_eln = xp.where(rst, xp.zeros_like(n_eln), n_eln)
        n_evh = xp.where(rst, xp.zeros_like(n_evh), n_evh)
        n_ev = xp.where(rst[:, :, None], xp.zeros_like(n_ev), n_ev)
        n_nlv = xp.where(any_match, xp.ones_like(ncand), ncand)
        n_skip = xp.where(any_match, ets, skip)
    else:
        n_nlv = ncand
        n_skip = skip

    # pad target shapes to M_out, then keep non-stepping keys untouched
    def merge(new, old, fill):
        if new.shape[1] < M_out or old.shape[1] < M_out:
            pad_n = M_out - new.shape[1]
            pad_o = M_out - old.shape[1]
            if pad_n:
                new = xp.concatenate(
                    [new, xp.full((Ka, pad_n) + new.shape[2:], fill,
                                  new.dtype)], axis=1)
            if pad_o:
                old = xp.concatenate(
                    [old, xp.full((Ka, pad_o) + old.shape[2:], fill,
                                  old.dtype)], axis=1)
        cond = stepping[:, None]
        if new.ndim == 3:
            cond = cond[:, :, None]
        return xp.where(cond, new, old)

    # mask dead trailing slots to the pristine pattern so stale payloads
    # never alias into a later comparison or snapshot
    slot = xp.arange(M_out, dtype=np.int32)[None, :]
    dead = slot >= n_nlv[:, None]
    n_st = xp.where(dead, xp.zeros_like(n_st), n_st)
    n_cnt = xp.where(dead, xp.zeros_like(n_cnt), n_cnt)
    n_fst = xp.where(dead, xp.full_like(n_fst, LONG_MIN), n_fst)
    n_eln = xp.where(dead, xp.zeros_like(n_eln), n_eln)
    n_evh = xp.where(dead, xp.zeros_like(n_evh), n_evh)
    n_ev = xp.where(dead[:, :, None], xp.zeros_like(n_ev), n_ev)

    new_block = (
        merge(n_st, st, np.int32(0)),
        merge(n_cnt, cnt, np.int32(0)),
        merge(n_fst, fst, np.int64(LONG_MIN)),
        merge(n_eln, eln, np.int32(0)),
        merge(n_ev, ev, np.int64(0)),
        merge(n_evh, evh, np.int32(0)),
        xp.where(stepping, n_nlv, nlv),
        n_skip,
    )
    return new_block, overflow_m


# ---------------------------------------------------------------------------
# numpy driver: exact dedup + growth + match extraction
# ---------------------------------------------------------------------------

def _exact_dup(cand, dup_pre: np.ndarray) -> np.ndarray:
    """Resolve the hash prefilter to EXACT duplicates (the interpreted
    ``seen`` key is (stage, count, events, greedy_from); greedy_from is
    always -1 for eligible patterns)."""
    if not dup_pre.any():
        return dup_pre
    dup = np.zeros_like(dup_pre)
    h = _cand_hash(np, cand)
    eligible = cand["valid"] & ~cand["ismatch"]
    for k, c in np.argwhere(dup_pre):
        hc = h[k, c]
        for c2 in range(c):
            if not eligible[k, c2] or h[k, c2] != hc or dup[k, c2]:
                continue
            if (cand["st"][k, c2] == cand["st"][k, c]
                    and cand["cnt"][k, c2] == cand["cnt"][k, c]
                    and cand["eln"][k, c2] == cand["eln"][k, c]):
                n = int(cand["eln"][k, c])
                if np.array_equal(cand["ev"][k, c2, :n],
                                  cand["ev"][k, c, :n]):
                    dup[k, c] = True
                    break
    return dup


class StepResult:
    """One event step's outcome: the new block plus match extraction."""

    __slots__ = ("block", "match_kc", "match_ev", "match_eln")

    def __init__(self, block, match_kc, match_ev, match_eln):
        self.block = block
        self.match_kc = match_kc       # [n, 2] (key row, candidate order)
        self.match_ev = match_ev       # list of packed int64 rows
        self.match_eln = match_eln


def step_numpy(tab: TransitionTable, m_cap: int, block, inputs
               ) -> Tuple[StepResult, int]:
    """One exact event step on the numpy backend.  Returns the result and
    the (possibly grown) partial capacity — E growth is handled internally
    by re-running the candidate pass on widened rings."""
    while True:
        cand, stepping, overflow_e = _candidates(np, tab, block, inputs)
        if bool(overflow_e):
            block = grow_event_ring(block)
            continue
        break
    sus = _dup_candidate_rows(cand)
    dup = np.zeros_like(cand["valid"])
    if sus.size:
        sub = {k: v[sus] for k, v in cand.items()}
        dup[sus] = _exact_dup(sub, _dup_prefilter(np, sub))
    keep = cand["valid"] & ~cand["ismatch"] & ~dup
    need = int(keep.sum(axis=1).max(initial=0))
    m_out = m_cap
    while need > m_out:
        m_out *= 2
    new_block, _ = _finalize(np, m_out, cand, dup, block, stepping,
                             inputs[1], tab.skip_past)
    mm = cand["ismatch"] & cand["valid"]
    kc = np.argwhere(mm)               # row-major: candidate order per key
    evs, elns = [], []
    for k, c in kc:
        n = int(cand["eln"][k, c])
        evs.append(np.array(cand["ev"][k, c, :n], np.int64))
        elns.append(n)
    return StepResult(new_block, kc, evs, elns), m_out


def grow_event_ring(block):
    """Double the bounded event-pointer ring (sticky high-water)."""
    st, cnt, fst, eln, ev, evh, nlv, skip = block
    Ka, M, E = ev.shape
    wide = np.zeros((Ka, M, max(2 * E, 2)), np.int64)
    wide[:, :, :E] = ev
    return (st, cnt, fst, eln, wide, evh, nlv, skip)


def grow_partials(block, m_new: int):
    """Widen the partial axis to ``m_new`` slots (sticky high-water)."""
    st, cnt, fst, eln, ev, evh, nlv, skip = block
    Ka, M, E = ev.shape
    if m_new <= M:
        return block
    pad = m_new - M

    def w(a, fill):
        return np.concatenate(
            [a, np.full((Ka, pad) + a.shape[2:], fill, a.dtype)], axis=1)

    return (w(st, 0), w(cnt, 0), w(fst, LONG_MIN), w(eln, 0),
            w(ev, 0), w(evh, 0), nlv, skip)


# ---------------------------------------------------------------------------
# jit driver: same step under jax.jit, numpy replay on dup/overflow
# ---------------------------------------------------------------------------

_jit_cache: Dict[Tuple, Any] = {}
_jit_lock = threading.Lock()
_JIT_CACHE_MAX = 64


def _table_key(tab: TransitionTable) -> Tuple:
    """Content key for the jit cache: identical patterns share compiled
    steps across operators and restores (an ``id()`` key would recompile
    per operator and pin dead tables forever)."""
    return (tab.n_stages, tuple(tab.strict.tolist()),
            tuple(tab.negated.tolist()), tuple(tab.optional.tolist()),
            tuple(tab.tmin.tolist()), tuple(tab.tmax.tolist()),
            tab.within, tab.skip_past, tab.trailing_negation,
            tab.has_until)


def _make_jit_step(tab: TransitionTable, m_cap: int, e_cap: int):
    """Compile one event step for fixed (M, E) shapes.  The jitted step
    returns the new block plus the candidate match planes and the
    dup/overflow flags; the caller replays flagged steps on the numpy
    path (exact dedup, ring growth) so results stay bit-identical."""
    import jax
    import jax.numpy as jnp

    key = (_table_key(tab), m_cap, e_cap)
    with _jit_lock:
        fn = _jit_cache.get(key)
        if fn is not None:
            return fn

    def step(st, cnt, fst, eln, ev, evh, nlv, skip,
             active, ets, eid, bits, ubits):
        block = (st, cnt, fst, eln, ev, evh, nlv, skip)
        inputs = (active, ets, eid, bits, ubits)
        cand, stepping, overflow_e = _candidates(jnp, tab, block, inputs)
        dup = _dup_prefilter(jnp, cand)
        keep = cand["valid"] & ~cand["ismatch"] & ~dup
        overflow_m = jnp.max(keep.sum(axis=1)) > m_cap
        new_block, _ = _finalize(jnp, m_cap, cand, dup, block, stepping,
                                 ets, tab.skip_past)
        mm = cand["ismatch"] & cand["valid"]
        # any hash-prefilter hit replays on the host: the jit never
        # commits a dedup decision that was not exactly verified
        flags = jnp.stack([overflow_e, overflow_m, jnp.any(dup)])
        return new_block, mm, cand["ev"], cand["eln"], flags

    with jax.enable_x64():
        jitted = jax.jit(step)
    with _jit_lock:
        while len(_jit_cache) >= _JIT_CACHE_MAX:   # bounded: FIFO evict
            _jit_cache.pop(next(iter(_jit_cache)))
        _jit_cache[key] = jitted
    return jitted


def step_jit(tab: TransitionTable, m_cap: int, block, inputs
             ) -> Tuple[StepResult, int]:
    """One event step via the jitted kernel; falls back to
    :func:`step_numpy` when the dispatch flags dup/overflow."""
    import jax

    e_cap = block[4].shape[2]
    fn = _make_jit_step(tab, m_cap, e_cap)
    with jax.enable_x64():
        new_block, mm, c_ev, c_eln, flags = fn(*block, *inputs)
        flags = np.asarray(flags)
        if flags.any():
            return step_numpy(tab, m_cap, block, inputs)
        mm = np.asarray(mm)
        if mm.any():
            c_ev = np.asarray(c_ev)
            c_eln = np.asarray(c_eln)
            kc = np.argwhere(mm)
            evs = [np.array(c_ev[k, c, :int(c_eln[k, c])], np.int64)
                   for k, c in kc]
            elns = [int(c_eln[k, c]) for k, c in kc]
        else:
            kc = np.empty((0, 2), np.int64)
            evs, elns = [], []
        new_block = tuple(np.asarray(a) for a in new_block)
    return StepResult(new_block, kc, evs, elns), m_cap


def default_kernel() -> str:
    """Kernel backend pick: ``FLINK_TPU_CEP_KERNEL=numpy|jit`` overrides;
    otherwise jit on accelerators, numpy on CPU (the XLA per-step dispatch
    loses to one fused numpy pass there)."""
    env = os.environ.get(_ENV_KERNEL, "").lower()
    if env in ("numpy", "np", "host"):
        return "numpy"
    if env in ("jit", "jax", "device"):
        return "jit"
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:  # noqa: BLE001 — jax unavailable/uninitialized
        return "numpy"
    return "numpy" if platform == "cpu" else "jit"


# ---------------------------------------------------------------------------
# engine calibration (a measured A/B, once per process)
# ---------------------------------------------------------------------------

_calibrated: Optional[bool] = None
_calib_lock = threading.Lock()


def calibrated_vectorized_cep() -> bool:
    """MEASURED verdict, cached process-wide: does the batched kernel beat
    the interpreted NFA on this host/backend?  ``vectorized="auto"`` asks
    this once; ``FLINK_TPU_CEP_VECTORIZED=on|off`` short-circuits."""
    global _calibrated
    if _calibrated is not None:
        return _calibrated
    with _calib_lock:
        if _calibrated is not None:
            return _calibrated
        env = os.environ.get(_ENV_ENGINE, "").lower()
        if env in ("on", "1", "true"):
            _calibrated = True
            return True
        if env in ("off", "0", "false"):
            _calibrated = False
            return False
        _calibrated = _measure_vectorized()
        return _calibrated


def _reset_calibration() -> None:
    """Test hook: drop the cached verdict."""
    global _calibrated
    with _calib_lock:
        _calibrated = None


def _measure_vectorized() -> bool:
    """A/B one synthetic drain (4k keys x 4 events, 2-stage pattern)
    through both engines; ties go to the kernel (it scales with keys,
    the interpreted loop does not)."""
    import time

    from flink_tpu.cep.operator import CepOperator
    from flink_tpu.core.batch import RecordBatch, Watermark

    def build(mode):
        pat = (Pattern.begin("a")
               .where(lambda c: np.asarray(c["v"]) < 0.25)
               .followed_by("b")
               .where(lambda c: np.asarray(c["v"]) > 0.75))
        return CepOperator(pat, "k", lambda m: {"n": 1}, vectorized=mode)

    rng = np.random.default_rng(41)
    n_keys, n_ev = 4096, 4
    keys = np.repeat(np.arange(n_keys, dtype=np.int64), n_ev)
    rng.shuffle(keys)
    vals = rng.random(keys.size)
    ts = np.arange(keys.size, dtype=np.int64)

    def run(mode):
        op = build(mode)
        t0 = time.perf_counter()
        op.process_batch(RecordBatch({"k": keys, "v": vals}, timestamps=ts))
        op.process_watermark(Watermark(int(ts[-1])))
        return time.perf_counter() - t0

    run("on")                    # warm compiles/caches outside the timing
    t_vec = min(run("on") for _ in range(2))
    t_int = min(run("off") for _ in range(2))
    return t_vec <= t_int


# ---------------------------------------------------------------------------
# interpreted-state bridge (degrade / snapshots / restore)
# ---------------------------------------------------------------------------

def encode_partials(partials, m_cap: int, e_cap: int):
    """Interpreted ``_Partial`` list -> one key's row planes (grown caps
    returned alongside; callers fold them into the sticky high-water)."""
    n = len(partials)
    while m_cap < max(n, 1):
        m_cap *= 2
    longest = max((len(p.events) for p in partials), default=0)
    while e_cap < max(longest, 1):
        e_cap *= 2
    st = np.zeros(m_cap, np.int32)
    cnt = np.zeros(m_cap, np.int32)
    fst = np.full(m_cap, LONG_MIN, np.int64)
    eln = np.zeros(m_cap, np.int32)
    ev = np.zeros((m_cap, e_cap), np.int64)
    evh = np.zeros(m_cap, np.int32)
    for m, p in enumerate(partials):
        st[m] = p.stage_i
        cnt[m] = p.count
        fst[m] = p.first_ts
        eln[m] = len(p.events)
        for e, (stage, eid) in enumerate(p.events):
            ev[m, e] = pack_event(stage, eid)
        evh[m] = event_list_hash(ev[m, :eln[m]])
    return (st, cnt, fst, eln, ev, evh, np.int32(n)), m_cap, e_cap


def decode_partials(row_block, nlive: int):
    """One key's row planes -> the interpreted ``_Partial`` list."""
    from flink_tpu.cep.operator import _Partial

    st, cnt, fst, eln, ev = row_block[:5]
    out = []
    for m in range(int(nlive)):
        out.append(_Partial(int(st[m]), int(cnt[m]),
                            unpack_events(ev[m, :int(eln[m])]),
                            int(fst[m])))
    return out
