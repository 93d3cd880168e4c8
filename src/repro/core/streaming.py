"""Streaming coreset construction (paper Alg. 2 "StreamCoreset" + the
tau-controlled doubling variant of §5.2), as a single jit'd lax.scan.

The scan is exposed as a resumable *ingestion API* — the substrate of the
online serving layer (serve/diversity):

    st = init_stream_state(d, gamma, spec, k, tau)
    st = ingest_batch(st, batch, cats, valid, spec, caps, k, tau,
                      base_index=offset)     # any number of times
    coreset = snapshot_coreset(st)

``stream_coreset`` (the one-shot entry point) is now a thin wrapper over
these three; batched ingestion is bit-identical to a single pass because the
scan branches only on ``st.n_seen``.

The scan is *blocked*: each step consumes ``block_size`` points. One
fused distance+classification pass (``kernels.ops.center_precheck``) plus a
matroid-specific precheck classifies every point in the block as a no-op
(within threshold of an existing center AND its HANDLE would not add a
delegate) or as active; runs of no-ops are consumed with O(1) masked
updates and only active points — center opens, delegate adds, restructures,
the first two stream points, and anything within the distance kernel's
error margin of a decision boundary — replay the exact per-point step.
``block_size=1`` recovers the original per-point scan; both produce
bit-identical states (asserted by the equivalence/property tests).

The per-point step itself is *branchless*: every decision (open a center,
add a delegate, shrink, merge a dead center's delegate) is computed as a
mask and applied as a dense ``jnp.where``-selected update instead of a
``lax.cond`` ladder. Under ``vmap``/``shard_map`` a batched ``lax.cond``
lowers to select-both-branches, so the historical cond ladder made every
shard pay every branch of every step; the masked form pays each update
exactly once. The rare *expensive* branches (restructure merges) stay real
branches via ``_cond_once`` — a single-trip ``lax.while_loop``, which vmap
keeps conditional (zero trips when no lane triggers). The historical
cond-ladder step is retained as ``step_impl="reference"`` — the bit-exact
Alg.-2 semantics the branchless scan is defined by and tested against
(tests/test_branchless_scan.py).

Sharded ingestion has two drives over the same per-shard scan:

* ``ingest_batch_sharded`` — ``jit(vmap)`` over a leading shard axis
  (single-device; the branchless step is what makes this fast);
* ``ingest_batch_sharded_mapped`` — ``shard_map`` over a 1-D device mesh
  (per-device shard groups run as independent programs, vmapping only the
  shards local to each device).

Per §3 composability (and the MapReduce formulation of arXiv:1605.05590),
shards build coresets independently and compose by union — see
``core/compose.py`` for the union/merge half and placement resolution.

State (all static shapes; TCAP centers, SLOT delegate slots per center):
  R          scalar estimate (diameter for Alg. 2; radius for the variant)
  x1         first stream point (Alg. 2's anchor for the diameter estimate)
  centers    f32[TCAP, d], cvalid bool[TCAP]
  del_*      delegate buffers per center: points f32[TCAP, SLOT, d],
             cats int32[TCAP, SLOT, gamma], valid bool[TCAP, SLOT],
             src int32[TCAP, SLOT]

Per point: nearest center; if farther than the new-center threshold, open a
center (the point is its own first delegate — Alg. 2); else HANDLE(x, z).
HANDLE is matroid-specific and matches Alg. 2 case-by-case:
  partition    add iff |D_z| < k and cat-count < cap (D_z stays independent)
  uniform      add iff |D_z| < k
  transversal  add iff some category of x has < k delegates; then try the
               shrink step with a *greedy* matching witness (a greedy size-k
               matching proves an independent size-k subset exists; sound,
               possibly later than the paper's exact check — DESIGN.md §8)
Restructuring merges dropped centers' delegates into their nearest survivor
via the same HANDLE (Alg. 2's merge loop).

General matroids need a host oracle => use ``stream_coreset_host`` (plain
python loop; streaming is single-machine in the paper anyway).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .coreset import Coreset
from .matroid import MatroidSpec

_BIG = jnp.float32(jnp.finfo(jnp.float32).max)

STEP_IMPLS = ("branchless", "reference")


class StreamState(NamedTuple):
    R: jnp.ndarray
    x1: jnp.ndarray  # (d,)
    n_seen: jnp.ndarray  # int32, number of (valid) points consumed
    centers: jnp.ndarray  # (TCAP, d)
    cvalid: jnp.ndarray  # (TCAP,)
    dp: jnp.ndarray  # (TCAP, SLOT, d)
    dc: jnp.ndarray  # (TCAP, SLOT, gamma)
    dv: jnp.ndarray  # (TCAP, SLOT)
    ds: jnp.ndarray  # (TCAP, SLOT)
    overflow: jnp.ndarray  # int32: forced-discard count (transversal cap)


def _dists_to_centers(x, centers, cvalid):
    diff = centers - x[None, :]
    d2 = jnp.sum(diff * diff, axis=-1)
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    return jnp.where(cvalid, d, _BIG)


def _cond_once(pred, fn, st):
    """``lax.cond(pred, fn, id)`` that stays a *real* branch under vmap.

    A batched ``lax.cond`` lowers to select-both-branches; a batched
    ``lax.while_loop`` executes its body only while some lane's predicate
    holds (with per-lane masking of the results). Wrapping a rarely-taken,
    expensive branch in a single-trip while_loop therefore keeps its skip
    under vmap — steps where no lane triggers pay nothing — while staying
    bit-identical to the cond form.
    """

    def body(carry):
        s, flag = carry
        return fn(s), jnp.zeros_like(flag)

    out, _ = jax.lax.while_loop(lambda c: c[1], body, (st, pred))
    return out


# --------------------------------------------------------------------------
# branchless masked primitives (the default scan)
# --------------------------------------------------------------------------


def _open_center_masked(st: StreamState, x, xc, xsrc, enable) -> StreamState:
    """Open a center at the first free slot iff ``enable``; otherwise every
    write puts the existing value back (a bit-exact no-op)."""
    slot = jnp.argmin(st.cvalid)  # first invalid center (all valid -> 0)
    return st._replace(
        centers=st.centers.at[slot].set(
            jnp.where(enable, x, st.centers[slot])
        ),
        cvalid=st.cvalid.at[slot].set(st.cvalid[slot] | enable),
        dp=st.dp.at[slot, 0].set(jnp.where(enable, x, st.dp[slot, 0])),
        dc=st.dc.at[slot, 0].set(jnp.where(enable, xc, st.dc[slot, 0])),
        dv=st.dv.at[slot, 0].set(st.dv[slot, 0] | enable),
        ds=st.ds.at[slot, 0].set(jnp.where(enable, xsrc, st.ds[slot, 0])),
    )


def _handle_masked(
    spec: MatroidSpec, k: int, caps, st: StreamState, z, x, xc, xsrc, enable
) -> tuple[StreamState, jnp.ndarray]:
    """Alg. 2 HANDLE(x, z, D_z) as masked dense updates.

    The add decision and its one-cell writes are computed unconditionally
    (cheap gathers/reductions over one center's slot buffer), ``where``-
    masked per field so lanes that didn't trigger stay bit-exact; for
    transversal, the greedy-matching shrink that follows a successful add
    runs under a ``_cond_once`` guard over that center's slot row only, so
    a rejected or disabled HANDLE costs no matching even under vmap.
    Returns ``(state, add)`` — ``add`` is the did-anything-change bit the
    blocked scan uses to decide precheck staleness.
    """
    slots_v = st.dv[z]  # (SLOT,)
    cnt = jnp.sum(slots_v.astype(jnp.int32))
    free_slot = jnp.argmin(slots_v)  # first False (all True -> 0, guarded)
    has_room = ~jnp.all(slots_v)

    if spec.kind == "uniform":
        add = cnt < k
        forced = jnp.int32(0)
    elif spec.kind == "partition":
        c = xc[0]
        same = slots_v & (st.dc[z, :, 0] == c)
        add = (cnt < k) & (jnp.sum(same.astype(jnp.int32)) < caps[c])
        forced = jnp.int32(0)
    elif spec.kind == "transversal":
        # count of delegates holding each category of x
        match = (st.dc[z][:, :, None] == xc[None, None, :]) & (
            xc[None, None, :] >= 0
        )  # (SLOT, gamma, gamma_x)
        holds = jnp.any(match, axis=1) & slots_v[:, None]  # (SLOT, gamma_x)
        cnts = jnp.sum(holds.astype(jnp.int32), axis=0)  # (gamma_x,)
        short = (cnts < k) & (xc >= 0)
        want = jnp.any(short)
        forced = (want & ~has_room & enable).astype(jnp.int32)
        add = want
    else:  # pragma: no cover
        raise ValueError(f"jit HANDLE not defined for {spec.kind!r}")

    add = add & has_room & enable
    # the add is one masked cell per buffer, written unconditionally: a
    # branch here would be a batched while under vmap, whose carry select
    # rewrites the whole state on every point
    st = st._replace(
        overflow=st.overflow + forced,
        dp=st.dp.at[z, free_slot].set(
            jnp.where(add, x, st.dp[z, free_slot])
        ),
        dc=st.dc.at[z, free_slot].set(
            jnp.where(add, xc, st.dc[z, free_slot])
        ),
        dv=st.dv.at[z, free_slot].set(st.dv[z, free_slot] | add),
        ds=st.ds.at[z, free_slot].set(
            jnp.where(add, xsrc, st.ds[z, free_slot])
        ),
    )
    if spec.kind == "transversal":
        # masked shrink: a greedy matching covering k slots is a witnessed
        # independent size-k subset — keep exactly those slots (post-add
        # buffers, like the historical cond'd _shrink). The matching stays
        # a real branch, carrying only the center's slot row.
        from .solvers.matching import greedy_matching_slots

        dcz = st.dc[z]

        def shrink(row):
            _used, matched = greedy_matching_slots(
                dcz, row, spec.num_categories
            )
            size = jnp.sum(matched.astype(jnp.int32))
            return jnp.where(size >= k, matched & row, row)

        st = st._replace(dv=st.dv.at[z].set(_cond_once(add, shrink, st.dv[z])))
    return st, add


def _merge_delegates(spec, k, caps, st: StreamState, dead_mask):
    """Alg. 2 restructure merge: delegates of dropped centers are HANDLE'd
    into their nearest surviving center.

    The tcap*slot fori_loop runs only when some center actually died — the
    ``_cond_once`` guard keeps that skip real even under vmap (a filter pass
    that keeps every center must not pay the merge loop on the scan's
    steady-state steps). The loop body itself is branchless: distance +
    masked HANDLE per slot."""
    tcap, slot_n = st.dv.shape

    def per_slot(i, st):
        ci, si = i // slot_n, i % slot_n
        en = dead_mask[ci] & st.dv[ci, si]
        x = st.dp[ci, si]
        d = _dists_to_centers(x, st.centers, st.cvalid)
        z = jnp.argmin(d)
        st, _add = _handle_masked(
            spec, k, caps, st, z, x, st.dc[ci, si], st.ds[ci, si], en
        )
        return st

    def run_merge(st: StreamState) -> StreamState:
        st = jax.lax.fori_loop(0, tcap * slot_n, per_slot, st)
        # clear dropped centers' own buffers
        return st._replace(dv=st.dv & ~dead_mask[:, None])

    return _cond_once(jnp.any(dead_mask), run_merge, st)


# --------------------------------------------------------------------------
# reference cond-ladder primitives (``step_impl="reference"``)
#
# The historical per-point step, kept verbatim: nested lax.cond dispatch on
# (first | second | general), cond'd HANDLE add + shrink, cond'd merge loop.
# This is the bit-exact Alg.-2 semantics the branchless step is defined by;
# tests/test_branchless_scan.py asserts field-for-field state identity
# between the two across matroid kinds, variants, block sizes and shards.
# --------------------------------------------------------------------------


def _handle_ref(spec: MatroidSpec, k: int, caps, st: StreamState, z, x, xc,
                xsrc):
    """Alg. 2 HANDLE(x, z, D_z). Returns updated state (+overflow count)."""
    slots_v = st.dv[z]  # (SLOT,)
    cnt = jnp.sum(slots_v.astype(jnp.int32))
    free_slot = jnp.argmin(slots_v)  # first False (all True -> 0, guarded)
    has_room = ~jnp.all(slots_v)

    if spec.kind == "uniform":
        add = cnt < k
        forced = jnp.int32(0)
    elif spec.kind == "partition":
        c = xc[0]
        same = slots_v & (st.dc[z, :, 0] == c)
        add = (cnt < k) & (jnp.sum(same.astype(jnp.int32)) < caps[c])
        forced = jnp.int32(0)
    elif spec.kind == "transversal":
        match = (st.dc[z][:, :, None] == xc[None, None, :]) & (
            xc[None, None, :] >= 0
        )  # (SLOT, gamma, gamma_x)
        holds = jnp.any(match, axis=1) & slots_v[:, None]  # (SLOT, gamma_x)
        cnts = jnp.sum(holds.astype(jnp.int32), axis=0)  # (gamma_x,)
        short = (cnts < k) & (xc >= 0)
        want = jnp.any(short)
        add = want & has_room
        forced = (want & ~has_room).astype(jnp.int32)
    else:  # pragma: no cover
        raise ValueError(f"jit HANDLE not defined for {spec.kind!r}")

    add = add & has_room

    def do_add(st: StreamState) -> StreamState:
        return st._replace(
            dp=st.dp.at[z, free_slot].set(x),
            dc=st.dc.at[z, free_slot].set(xc),
            dv=st.dv.at[z, free_slot].set(True),
            ds=st.ds.at[z, free_slot].set(xsrc),
        )

    st = jax.lax.cond(add, do_add, lambda s: s, st)
    st = st._replace(overflow=st.overflow + forced)

    if spec.kind == "transversal":
        st = jax.lax.cond(
            add, lambda s: _shrink_ref(spec, k, s, z), lambda s: s, st
        )
    return st


def _shrink_ref(spec: MatroidSpec, k: int, st: StreamState, z):
    """Greedy-matching shrink: if a greedy matching of D_z covers k slots,
    keep exactly those slots (a witnessed independent set of size k)."""
    from .solvers.matching import greedy_matching_slots

    slots_v = st.dv[z]
    _used, matched = greedy_matching_slots(
        st.dc[z], slots_v, spec.num_categories
    )
    size = jnp.sum(matched.astype(jnp.int32))

    def do_shrink(st: StreamState) -> StreamState:
        return st._replace(dv=st.dv.at[z].set(matched & slots_v))

    return jax.lax.cond(size >= k, do_shrink, lambda s: s, st)


def _merge_delegates_ref(spec, k, caps, st: StreamState, dead_mask):
    """The cond-ladder restructure merge (reference semantics)."""
    tcap, slot_n = st.dv.shape

    def per_slot(i, st):
        ci, si = i // slot_n, i % slot_n
        is_live_del = dead_mask[ci] & st.dv[ci, si]

        def do(st: StreamState) -> StreamState:
            x = st.dp[ci, si]
            d = _dists_to_centers(x, st.centers, st.cvalid)
            z = jnp.argmin(d)
            return _handle_ref(
                spec, k, caps, st, z, x, st.dc[ci, si], st.ds[ci, si]
            )

        return jax.lax.cond(is_live_del, do, lambda s: s, st)

    def run_merge(st: StreamState) -> StreamState:
        st = jax.lax.fori_loop(0, tcap * slot_n, per_slot, st)
        return st._replace(dv=st.dv & ~dead_mask[:, None])

    return jax.lax.cond(jnp.any(dead_mask), run_merge, lambda s: s, st)


def _filter_centers(st: StreamState, thr):
    """Greedy maximal subset of centers with pairwise distance > thr."""
    c = st.centers
    d2 = jnp.sum((c[:, None, :] - c[None, :, :]) ** 2, axis=-1)
    d = jnp.sqrt(jnp.maximum(d2, 0.0))
    tcap = c.shape[0]

    def body(i, keep):
        near_kept = jnp.any(keep & st.cvalid & (d[i] <= thr) &
                            (jnp.arange(tcap) < i))
        ki = st.cvalid[i] & ~near_kept
        return keep.at[i].set(ki)

    keep = jax.lax.fori_loop(0, tcap, body, jnp.zeros((tcap,), bool))
    return keep


def default_slot_cap(spec: MatroidSpec, k: int) -> int:
    """Static per-center delegate capacity (Alg. 2 size bounds)."""
    if spec.kind in ("uniform", "partition"):
        return k
    return max(spec.gamma, 1) * k * k


def init_stream_state(
    d: int,
    gamma: int,
    spec: MatroidSpec,
    k: int,
    tau: int,
    *,
    slot_cap: Optional[int] = None,
) -> StreamState:
    """Empty resumable scan state (the ingestion API's starting point).

    The returned state is a pure pytree of static-shape buffers: feed it to
    ``ingest_batch`` any number of times, snapshot with ``snapshot_coreset``.

    ``tau >= 2``: the scan unconditionally opens centers for the first two
    stream points (Alg. 2's anchors) before any restructure can run, so a
    smaller tau could enter a general step already over budget — a state
    the radius-variant restructure bookkeeping (and the blocked scan's
    "an over-tau count only follows an open" staleness invariant) is
    allowed to assume impossible.
    """
    if tau < 2:
        raise ValueError(f"tau must be >= 2, got {tau}")
    tcap = tau + 1
    if slot_cap is None:
        slot_cap = default_slot_cap(spec, k)
    return StreamState(
        R=jnp.float32(0.0),
        x1=jnp.zeros((d,), jnp.float32),
        n_seen=jnp.int32(0),
        centers=jnp.zeros((tcap, d), jnp.float32),
        cvalid=jnp.zeros((tcap,), bool),
        dp=jnp.zeros((tcap, slot_cap, d), jnp.float32),
        dc=jnp.full((tcap, slot_cap, gamma), -1, jnp.int32),
        dv=jnp.zeros((tcap, slot_cap), bool),
        ds=jnp.full((tcap, slot_cap), -1, jnp.int32),
        overflow=jnp.int32(0),
    )


def _epoch_stats_impl(st: StreamState):
    """Device-side epoch statistics of a scan state: ``(count, h1, h2)``.

    The coreset is determined by which ``(center, slot)`` cells are live and
    which stream row each holds, i.e. by ``(dv & cvalid, ds)``. Instead of
    pulling those buffers to the host and hashing them per ingest (the
    historical fingerprint — an O(buffers) host sync on the serving hot
    path), this reduces them *on device* to three scalars: the live-cell
    count (from the same per-center count tables the blocked precheck
    uses) plus two independent position-mixed uint32 checksums, so the
    epoch decision ("did the coreset change?") costs one O(1) host pull.
    Positions enter each sum through distinct odd multipliers, so moving a
    delegate between cells — or swapping two — changes the value; two
    checksums with different mixes make an accidental collision of a real
    change astronomically unlikely. Accepts a single state or a stacked
    per-shard state (the reductions flatten every leading axis).
    """
    with jax.named_scope("dmmc/epoch_stats"):
        valid = st.dv & st.cvalid[..., None]
        vz = valid.reshape(-1)
        src = jnp.where(vz, st.ds.reshape(-1).astype(jnp.uint32) + 1, 0)
        pos = jnp.arange(vz.shape[0], dtype=jnp.uint32)
        count = jnp.sum(jnp.sum(valid, axis=-1, dtype=jnp.int32))
        h1 = jnp.sum(
            src * (pos * jnp.uint32(0x9E3779B1) | 1), dtype=jnp.uint32
        )
        h2 = jnp.sum(
            (src ^ (pos * jnp.uint32(0x85EBCA6B))) * jnp.uint32(0x27D4EB2F),
            dtype=jnp.uint32,
        )
        return count, h1, h2


# Not donated: it must observe the live serving state without consuming it
# (the ingest entry points donate; this one only reads).
epoch_stats = jax.jit(_epoch_stats_impl)


def epoch_fingerprint(st: StreamState) -> tuple[int, int]:
    """Host ``(fingerprint, coreset_size)`` of a scan state via one O(1)
    device sync — the epoch-snapshot decision point of the serving runtime
    (``serve.diversity.StreamRuntime``): ingestion calls this per batch and
    publishes a new epoch only when the fingerprint moved."""
    count, h1, h2 = jax.device_get(epoch_stats(st))
    return hash((int(count), int(h1), int(h2))), int(count)


def state_to_arrays(st: StreamState) -> dict:
    """Serialize one ``StreamState`` to plain host arrays, field-keyed.

    The scan is a pure fold, so this dict — float32/int32/bool buffers
    pulled off the device — IS the resumable stream: round-tripping
    through ``state_from_arrays`` and resuming ingestion is bit-identical
    to never having serialized (pinned by the checkpoint/restore parity
    suite). Works on single and stacked (leading shard axis) states
    alike; the serving checkpoint layer (``serve.diversity.checkpoint``)
    handles the per-shard list of the pipeline placement.
    """
    return {f: np.asarray(getattr(st, f)) for f in StreamState._fields}


def state_from_arrays(arrays) -> StreamState:
    """Rebuild a device ``StreamState`` from ``state_to_arrays`` output
    (dtypes preserved exactly; missing fields raise ``KeyError``)."""
    return StreamState(
        **{f: jnp.asarray(np.asarray(arrays[f]))
           for f in StreamState._fields}
    )


def snapshot_coreset(st: StreamState) -> Coreset:
    """Assemble the current coreset from the delegate buffers (jit-safe)."""
    tcap, slot_cap, d = st.dp.shape
    gamma = st.dc.shape[2]
    flat_valid = st.dv.reshape(-1) & jnp.repeat(st.cvalid, slot_cap)
    return Coreset(
        points=st.dp.reshape(-1, d),
        cats=st.dc.reshape(-1, gamma),
        valid=flat_valid,
        src_idx=jnp.where(flat_valid, st.ds.reshape(-1), -1),
    )


def _make_step_branchless(spec: MatroidSpec, k: int, tau: int, caps_arr,
                          variant: str, eps: float, c_const: int):
    """Branchless masked-update per-point step (the default scan step).

    Every per-point decision becomes a mask over one dense update pass:
    distances/argmin are computed once, the (first | second | open | handle)
    cases are disjoint enables over masked writes, and ``n_seen`` advances
    by the validity bit. Only the restructure merges — rare and genuinely
    expensive — remain real branches, via ``_cond_once`` (vmap-skippable).
    Bit-identical to ``_make_step_reference`` (parity suite) because every
    masked-off write puts the existing value back.
    """

    def restructure_radius(st: StreamState) -> StreamState:
        """tau-variant: while #centers > tau: R *= 2; filter; merge."""

        def cond(st):
            return jnp.sum(st.cvalid.astype(jnp.int32)) > tau

        def body(st):
            R = st.R * 2.0
            st = st._replace(R=R)
            keep = _filter_centers(st, R)
            dead = st.cvalid & ~keep
            st = st._replace(cvalid=keep)
            return _merge_delegates(spec, k, caps_arr, st, dead)

        return jax.lax.while_loop(cond, body, st)

    def restructure_diameter(st: StreamState) -> StreamState:
        """Alg. 2: after R update, filter at eps*R/(ck) and merge."""
        thr = jnp.float32(eps) * st.R / (c_const * k)
        keep = _filter_centers(st, thr)
        dead = st.cvalid & ~keep
        st = st._replace(cvalid=keep)
        return _merge_delegates(spec, k, caps_arr, st, dead)

    def step(st: StreamState, inp):
        x, xc, xsrc, v = inp
        t = st.n_seen
        is_first = v & (t == 0)
        is_second = v & (t == 1)
        is_general = v & (t >= 2)

        # one distance pass against the pre-step centers (first/second lanes
        # read garbage here; their enables mask every use of it)
        dists = _dists_to_centers(x, st.centers, st.cvalid)
        z = jnp.argmin(dists)
        dmin = dists[z]
        if variant == "diameter":
            thr_new = 2.0 * eps * st.R / (c_const * k)
        else:
            thr_new = 2.0 * st.R
        opens = is_first | is_second | (is_general & (dmin > thr_new))
        handles = is_general & ~(dmin > thr_new)

        st = _cond_once(
            opens, lambda s: _open_center_masked(s, x, xc, xsrc, opens), st
        )
        st, added = _handle_masked(
            spec, k, caps_arr, st, z, x, xc, xsrc, handles
        )

        # first/second bookkeeping: anchor + initial estimate
        r0 = jnp.sqrt(jnp.maximum(jnp.sum((x - st.x1) ** 2), 0.0))
        R2 = r0 if variant == "diameter" else r0 / 2.0
        st = st._replace(
            R=jnp.where(is_second, jnp.maximum(R2, 1e-30), st.R),
            x1=jnp.where(is_first, x, st.x1),
        )

        if variant == "diameter":
            d1 = jnp.sqrt(jnp.maximum(jnp.sum((x - st.x1) ** 2), 0.0))
            trigger = is_general & (d1 > 2.0 * st.R)

            def upd(st):
                st = st._replace(R=d1)
                return restructure_diameter(st)

            st = _cond_once(trigger, upd, st)
            changed = opens | added | trigger
        else:
            need = is_general & (
                jnp.sum(st.cvalid.astype(jnp.int32)) > tau
            )
            st = _cond_once(need, restructure_radius, st)
            # an over-tau center count only ever follows an open this step,
            # so `opens` subsumes `need` in the changed bit
            changed = opens | added
        # `changed` is the precheck-staleness bit: True iff any field the
        # block precheck reads (centers/cvalid/dv/dc/R/x1) may have been
        # written. n_seen/overflow always advance but are not precheck
        # inputs.
        return st._replace(n_seen=t + v.astype(jnp.int32)), changed

    return step


def _make_step_reference(spec: MatroidSpec, k: int, tau: int, caps_arr,
                         variant: str, eps: float, c_const: int):
    """The historical cond-ladder per-point Alg.-2 scan step (the bit-exact
    reference semantics the branchless step is defined by)."""

    def open_center(st: StreamState, x, xc, xsrc) -> StreamState:
        slot = jnp.argmin(st.cvalid)
        return st._replace(
            centers=st.centers.at[slot].set(x),
            cvalid=st.cvalid.at[slot].set(True),
            dp=st.dp.at[slot, 0].set(x),
            dc=st.dc.at[slot, 0].set(xc),
            dv=st.dv.at[slot, 0].set(True),
            ds=st.ds.at[slot, 0].set(xsrc),
        )

    def restructure_radius(st: StreamState) -> StreamState:
        """tau-variant: while #centers > tau: R *= 2; filter; merge."""

        def cond(st):
            return jnp.sum(st.cvalid.astype(jnp.int32)) > tau

        def body(st):
            R = st.R * 2.0
            st = st._replace(R=R)
            keep = _filter_centers(st, R)
            dead = st.cvalid & ~keep
            st = st._replace(cvalid=keep)
            return _merge_delegates_ref(spec, k, caps_arr, st, dead)

        return jax.lax.while_loop(cond, body, st)

    def restructure_diameter(st: StreamState) -> StreamState:
        """Alg. 2: after R update, filter at eps*R/(ck) and merge."""
        thr = jnp.float32(eps) * st.R / (c_const * k)
        keep = _filter_centers(st, thr)
        dead = st.cvalid & ~keep
        st = st._replace(cvalid=keep)
        return _merge_delegates_ref(spec, k, caps_arr, st, dead)

    def step(st: StreamState, inp):
        x, xc, xsrc, v = inp
        t = st.n_seen

        def skip(st):
            return st

        def first(st: StreamState) -> StreamState:
            st = open_center(st, x, xc, xsrc)
            return st._replace(x1=x, n_seen=t + 1)

        def second(st: StreamState) -> StreamState:
            r0 = jnp.sqrt(
                jnp.maximum(jnp.sum((x - st.x1) ** 2), 0.0)
            )
            st = open_center(st, x, xc, xsrc)
            R = r0 if variant == "diameter" else r0 / 2.0
            return st._replace(R=jnp.maximum(R, 1e-30), n_seen=t + 1)

        def general(st: StreamState) -> StreamState:
            dists = _dists_to_centers(x, st.centers, st.cvalid)
            z = jnp.argmin(dists)
            dmin = dists[z]
            if variant == "diameter":
                thr_new = 2.0 * eps * st.R / (c_const * k)
            else:
                thr_new = 2.0 * st.R

            def as_new(st):
                return open_center(st, x, xc, xsrc)

            def as_handle(st):
                return _handle_ref(spec, k, caps_arr, st, z, x, xc, xsrc)

            st = jax.lax.cond(dmin > thr_new, as_new, as_handle, st)

            if variant == "diameter":
                d1 = jnp.sqrt(jnp.maximum(jnp.sum((x - st.x1) ** 2), 0.0))

                def upd(st):
                    st = st._replace(R=d1)
                    return restructure_diameter(st)

                st = jax.lax.cond(d1 > 2.0 * st.R, upd, lambda s: s, st)
            else:
                st = jax.lax.cond(
                    jnp.sum(st.cvalid.astype(jnp.int32)) > tau,
                    restructure_radius,
                    lambda s: s,
                    st,
                )
            return st._replace(n_seen=t + 1)

        branch = jnp.where(t == 0, 0, jnp.where(t == 1, 1, 2))
        st = jax.lax.cond(
            v,
            lambda st: jax.lax.switch(branch, [first, second, general], st),
            skip,
            st,
        )
        # conservative staleness bit: the reference impl always reports
        # "maybe changed", so the blocked scan re-prechecks every iteration
        # (the historical behavior)
        return st, jnp.bool_(True)

    return step


def _make_step(spec: MatroidSpec, k: int, tau: int, caps_arr, variant: str,
               eps: float, c_const: int, step_impl: str = "branchless"):
    """Build the per-point Alg.-2 scan step (``branchless`` masked-update
    default, or the historical ``reference`` cond ladder)."""
    if step_impl not in STEP_IMPLS:
        raise ValueError(
            f"step_impl must be one of {STEP_IMPLS}, got {step_impl!r}"
        )
    make = (
        _make_step_branchless
        if step_impl == "branchless"
        else _make_step_reference
    )
    return make(spec, k, tau, caps_arr, variant, eps, c_const)


def _block_precheck(spec: MatroidSpec, k: int, caps_arr, variant: str,
                    eps: float, c_const: int, st: StreamState,
                    xb, xcb, vb):
    """Vectorized would-this-point-change-state test for a block of points,
    evaluated against the *current* state.

    Returns (active bool[B], forced int32[B]). A point is active iff the
    per-point step would do anything beyond incrementing ``n_seen`` (and, for
    transversal, ``overflow``): open a center, add a delegate (incl. the
    shrink that follows), trigger the diameter-variant R update, or fall
    within the distance kernel's error margin of any of those decision
    boundaries. Inactive valid points are exact no-ops whose only effect is
    ``n_seen += 1`` and ``overflow += forced`` — the invariant the blocked
    scan's bulk-skip relies on (state-unchanged induction along the block).

    The distance + top-3-nearest classification is one fused op
    (``kernels.ops.center_precheck``: Pallas panel-matmul kernel on TPU,
    matmul-form jnp on CPU, the exact broadcast oracle under ``ref``), and
    the two candidate centers it returns are *exact-refined* here: a
    (B, 2, d) gather recomputes their distances with the per-point
    arithmetic, so the nearest-center choice and the open threshold are
    decided exactly and only two cases still fall back to the sequential
    replay — an exact tie between the two candidates (``jnp.argmin``'s
    first-index rule needs the full buffer order) and a third candidate
    within the matmul error margin of the refined minimum (the candidate
    pair might then not contain the true nearest).
    """
    from ..kernels import ops as _ops

    dmin_e, z1, _second_e, z2, third_e, margin = _ops.center_precheck(
        xb, st.centers, st.cvalid
    )
    d1e = jnp.sqrt(
        jnp.maximum(jnp.sum((st.centers[z1] - xb) ** 2, axis=-1), 0.0)
    )
    d2e = jnp.sqrt(
        jnp.maximum(jnp.sum((st.centers[z2] - xb) ** 2, axis=-1), 0.0)
    )
    d1e = jnp.where(st.cvalid[z1], d1e, _BIG)
    d2e = jnp.where(st.cvalid[z2], d2e, _BIG)
    z = jnp.where(d2e < d1e, z2, z1)
    dmin = jnp.minimum(d1e, d2e)
    # sequential-fallback cases: exact candidate tie, or the third-nearest
    # estimate within the error margin of the estimated minimum
    tie = (d1e == d2e) | ((third_e - dmin_e) <= 2.0 * margin)

    if variant == "diameter":
        thr_new = 2.0 * eps * st.R / (c_const * k)
    else:
        thr_new = 2.0 * st.R
    opens = dmin > thr_new

    # HANDLE classification via per-center count tables: O(T * SLOT) once
    # per block + O(B) scalar gathers, instead of gathering every row's
    # (SLOT[, gamma]) delegate buffers. Counts are integers, so the add
    # decisions are exactly the per-row sums the scan step computes.
    cnt_t = jnp.sum(st.dv.astype(jnp.int32), axis=1)  # (T,)
    full_t = jnp.all(st.dv, axis=1)  # (T,)
    cnt = cnt_t[z]
    has_room = ~full_t[z]
    # Rows whose labels fall outside the table range cannot be classified
    # by the count tables (a gather would clamp/wrap where the per-point
    # step compares `dc == c` exactly) — flag them active so the exact
    # replay decides, preserving bit-identity for arbitrary label input.
    if spec.kind == "uniform":
        add = cnt < k
        forced = jnp.zeros(xb.shape[0], jnp.int32)
        oob = jnp.zeros(xb.shape[0], bool)
    elif spec.kind == "partition":
        c = xcb[:, 0]
        h = max(spec.num_categories, 1)
        oob = (c < 0) | (c >= h)
        same_t = jnp.sum(
            (
                (st.dc[:, :, 0, None] == jnp.arange(h)[None, None, :])
                & st.dv[:, :, None]
            ).astype(jnp.int32),
            axis=1,
        )  # (T, h): delegates of center t in category c
        cs = jnp.clip(c, 0, h - 1)
        add = (cnt < k) & (same_t[z, cs] < caps_arr[cs])
        forced = jnp.zeros(xb.shape[0], jnp.int32)
    elif spec.kind == "transversal":
        h = max(spec.num_categories, 1)
        oob = jnp.any(xcb >= h, axis=1)  # -1 padding is masked below
        holds_t = jnp.any(
            st.dc[:, :, :, None] == jnp.arange(h)[None, None, None, :],
            axis=2,
        ) & st.dv[:, :, None]  # (T, SLOT, h): slot holds category
        cnt_th = jnp.sum(holds_t.astype(jnp.int32), axis=1)  # (T, h)
        cnts = cnt_th[z[:, None], jnp.clip(xcb, 0, h - 1)]  # (B, gamma_x)
        short = (cnts < k) & (xcb >= 0)
        want = jnp.any(short, axis=1)
        add = want & has_room
        forced = (want & ~has_room & ~oob).astype(jnp.int32)
    else:  # pragma: no cover
        raise ValueError(f"blocked scan not defined for {spec.kind!r}")
    add = add & has_room

    active = opens | add | tie | oob
    if variant == "diameter":
        # d1 is the per-point arithmetic itself (row-wise diff/square/sum),
        # so the R-update trigger is decided exactly — no margin needed
        d1 = jnp.sqrt(
            jnp.maximum(jnp.sum((xb - st.x1[None, :]) ** 2, axis=-1), 0.0)
        )
        active = active | (d1 > 2.0 * st.R)
    return active & vb, forced


def _blocked_scan(step, spec: MatroidSpec, k: int, caps_arr, variant: str,
                  eps: float, c_const: int, st0: StreamState,
                  points, cats, src, valid, block_size: int) -> StreamState:
    """Scan B points per step: one vectorized distance/precheck pass decides
    which points could change state; runs of no-op points are consumed in
    O(1) masked updates and only the (rare, in steady state) active points
    replay the exact per-point step — bit-identical to the per-point scan."""
    n, d = points.shape
    B = block_size
    pad = -n % B
    if pad:
        points = jnp.concatenate([points, jnp.zeros((pad, d), points.dtype)])
        cats = jnp.concatenate(
            [cats, jnp.full((pad, cats.shape[1]), -1, cats.dtype)]
        )
        src = jnp.concatenate([src, jnp.full((pad,), -1, jnp.int32)])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])
    nb = points.shape[0] // B
    Pb = points.reshape(nb, B, d)
    Cb = cats.reshape(nb, B, -1)
    Sb = src.reshape(nb, B)
    Vb = valid.reshape(nb, B)
    idx = jnp.arange(B, dtype=jnp.int32)

    def block_step(st: StreamState, inp):
        xb, xcb, srcb, vb = inp

        # one precheck against the block-entry state decides the whole
        # block when nothing is active (the steady-state case): the loop
        # below — whose batched-while carry select would copy every state
        # buffer per iteration under vmap — is entered only when some
        # point actually needs a sequential replay
        with jax.named_scope("dmmc/precheck"):
            active0, forced0 = _block_precheck(
                spec, k, caps_arr, variant, eps, c_const, st, xb, xcb, vb
            )
        excl0 = jnp.cumsum(vb.astype(jnp.int32)) - vb.astype(jnp.int32)
        any_act = jnp.any(active0 | (vb & (st.n_seen + excl0 < 2)))
        nv = jnp.sum(vb.astype(jnp.int32))
        fo = jnp.sum(jnp.where(vb, forced0, 0))
        st = st._replace(
            n_seen=st.n_seen + jnp.where(any_act, 0, nv),
            overflow=st.overflow + jnp.where(any_act, 0, fo),
        )

        def cond(carry):
            return carry[1] < B

        def body(carry):
            st, i, active, forced, dirty = carry

            # the precheck is a pure function of (centers, cvalid, dv, dc,
            # R, x1); replaying a point that changed none of them (a
            # margin-fallback no-op) leaves the cached classification
            # bit-identical, so only `dirty` iterations recompute it
            def recompute(_):
                return _block_precheck(
                    spec, k, caps_arr, variant, eps, c_const, st, xb, xcb,
                    vb,
                )

            active, forced = _cond_once(dirty, recompute, (active, forced))
            rem = idx >= i
            # the first two (valid) stream points take special branches
            vrem = vb & rem
            excl = jnp.cumsum(vrem.astype(jnp.int32)) - vrem.astype(jnp.int32)
            act = (active | (vrem & (st.n_seen + excl < 2))) & rem
            f = jnp.where(jnp.any(act), jnp.argmax(act), B).astype(jnp.int32)
            skip = vrem & (idx < f)
            st = st._replace(
                n_seen=st.n_seen + jnp.sum(skip.astype(jnp.int32)),
                overflow=st.overflow + jnp.sum(jnp.where(skip, forced, 0)),
            )
            fs = jnp.minimum(f, B - 1)  # clamped gather; guarded by f < B

            def do_point(carry):
                st, _ = carry
                return step(st, (xb[fs], xcb[fs], srcb[fs], vb[fs]))

            # _cond_once, not lax.cond: under vmap a cond pays the replay
            # step on every block iteration of every shard; the single-trip
            # while skips it for real whenever no lane found an active point
            st, changed = _cond_once(
                f < B, do_point, (st, jnp.bool_(False))
            )
            return st, f + 1, active, forced, changed

        def run_block(st: StreamState) -> StreamState:
            # seeded with the hoisted precheck (dirty=False: the state has
            # not changed since it was computed)
            st, _, _, _, _ = jax.lax.while_loop(
                cond,
                body,
                (st, jnp.int32(0), active0, forced0, jnp.bool_(False)),
            )
            return st

        st = _cond_once(any_act, run_block, st)
        return st, None

    with jax.named_scope("dmmc/blocked_scan"):
        st, _ = jax.lax.scan(block_step, st0, (Pb, Cb, Sb, Vb))
    return st


def _ingest_core(st0: StreamState, points, cats, valid, src,
                 spec: MatroidSpec, caps_arr, k: int, tau: int,
                 variant: str, eps: float, c_const: int,
                 block_size: int, step_impl: str) -> StreamState:
    step = _make_step(spec, k, tau, caps_arr, variant, eps, c_const,
                      step_impl)
    valid = valid.astype(bool)
    if block_size <= 1:
        st, _ = jax.lax.scan(
            lambda s, inp: (step(s, inp)[0], None),
            st0, (points, cats, src, valid),
        )
        return st
    return _blocked_scan(
        step, spec, k, caps_arr, variant, eps, c_const,
        st0, points, cats, src, valid, block_size,
    )


def _ingest_batch_impl(
    st0: StreamState,
    points: jnp.ndarray,
    cats: jnp.ndarray,
    valid: jnp.ndarray,
    spec: MatroidSpec,
    caps: Optional[jnp.ndarray],
    k: int,
    tau: int,
    *,
    base_index: jnp.ndarray = 0,
    variant: str = "radius",
    eps: float = 0.5,
    c_const: int = 32,
    block_size: int = 128,
    step_impl: str = "branchless",
    src: Optional[jnp.ndarray] = None,
) -> StreamState:
    n, _ = points.shape
    caps_arr = caps if caps is not None else jnp.zeros((1,), jnp.int32)
    if src is None:
        src = jnp.asarray(base_index, jnp.int32) + jnp.arange(
            n, dtype=jnp.int32
        )
    else:
        src = jnp.asarray(src, jnp.int32)
    return _ingest_core(
        st0, points, cats, valid, src, spec, caps_arr, k, tau,
        variant, eps, c_const, block_size, step_impl,
    )


_INGEST_STATICS = (
    "spec", "k", "tau", "variant", "c_const", "block_size", "step_impl"
)

ingest_batch = functools.partial(
    jax.jit, static_argnames=_INGEST_STATICS
)(_ingest_batch_impl)

# donated variant for resume-in-place callers (state reassigned every call,
# e.g. the serving layer): XLA aliases the old state's buffers into the new
# state's, so a steady-state ingest stops paying a full state copy per call
# — the dominant fixed cost once the scan itself is branchless. The donated
# input is consumed: only use when the passed state is dropped on return.
ingest_batch_donated = functools.partial(
    jax.jit, static_argnames=_INGEST_STATICS, donate_argnums=(0,)
)(_ingest_batch_impl)

ingest_batch.__doc__ = _ingest_batch_impl.__doc__ = (
    """Resume the jit'd Alg.-2 scan over one batch of the stream.

    ``st0`` is ``init_stream_state(...)`` or the state returned by a previous
    ``ingest_batch`` call; ``base_index`` offsets the delegates' ``src_idx``
    so they stay global across batches. The scan branches on ``st.n_seen``,
    so resuming mid-stream is exact: the concatenation of batches yields
    bit-identical state to a single one-shot pass.

    ``block_size`` > 1 selects the blocked scan (B points per step; the
    vectorized precheck bulk-skips no-op points and replays only state-
    changing ones through the per-point step) — bit-identical to
    ``block_size=1`` by construction; the equivalence tests parameterize
    over both. ``step_impl`` selects the branchless masked-update step
    (default) or the historical cond-ladder reference, themselves
    bit-identical (tests/test_branchless_scan.py). ``ingest_batch_donated``
    is the same function with the input state donated (serving hot path).
    """
)


def init_sharded_states(
    num_shards: int,
    d: int,
    gamma: int,
    spec: MatroidSpec,
    k: int,
    tau: int,
    *,
    slot_cap: Optional[int] = None,
) -> StreamState:
    """Stacked pytree of ``num_shards`` empty stream states (leading shard
    axis on every leaf) — the carry for ``ingest_batch_sharded``."""
    st = init_stream_state(d, gamma, spec, k, tau, slot_cap=slot_cap)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (num_shards,) + x.shape), st
    )


def _ingest_batch_sharded_impl(
    sts: StreamState,  # stacked: every leaf has leading shard axis S
    points: jnp.ndarray,  # (S, m, d)
    cats: jnp.ndarray,  # (S, m, gamma)
    valid: jnp.ndarray,  # (S, m)
    src: jnp.ndarray,  # (S, m) global stream indices
    spec: MatroidSpec,
    caps: Optional[jnp.ndarray],
    k: int,
    tau: int,
    *,
    variant: str = "radius",
    eps: float = 0.5,
    c_const: int = 32,
    block_size: int = 128,
    step_impl: str = "branchless",
) -> StreamState:
    caps_arr = caps if caps is not None else jnp.zeros((1,), jnp.int32)

    def one(st, p, c, v, s):
        return _ingest_core(
            st, p, c, v, s, spec, caps_arr, k, tau,
            variant, eps, c_const, block_size, step_impl,
        )

    return jax.vmap(one)(sts, points, cats, valid.astype(bool), src)


ingest_batch_sharded = functools.partial(
    jax.jit, static_argnames=_INGEST_STATICS
)(_ingest_batch_sharded_impl)

# donated variant (see ingest_batch_donated): a stacked shard state is S
# full StreamStates, so the per-call output copy it avoids is S times larger
ingest_batch_sharded_donated = functools.partial(
    jax.jit, static_argnames=_INGEST_STATICS, donate_argnums=(0,)
)(_ingest_batch_sharded_impl)

ingest_batch_sharded.__doc__ = _ingest_batch_sharded_impl.__doc__ = (
    """vmapped blocked ingestion: every shard runs its own independent
    Alg.-2 scan (paper §3 / the MapReduce formulation: coresets of a
    partition compose by union). Per-shard results are bit-identical to
    running ``ingest_batch`` on that shard's sub-stream alone.

    This is the single-device drive; the branchless step is what makes it
    fast (a vmapped cond ladder pays select-both-branches on every step).
    With more than one device, ``ingest_batch_sharded_mapped`` runs the
    shard groups as per-device programs instead.
    """
)


PLACEMENTS = ("auto", "vmap", "shard_map", "pipeline")


def resolve_placement(placement: str, num_shards: int) -> str:
    """Resolve the sharded-ingest drive.

    ``vmap``       one batched program over row-granular round-robin shard
                   sub-streams (single-accelerator drive: one launch covers
                   all shards; the branchless step is what makes it cheap);
    ``shard_map``  per-device shard groups over a 1-D mesh (multi-device
                   accelerator drive: real branches, real parallelism, one
                   SPMD launch);
    ``pipeline``   batch-granular round-robin over independent per-shard
                   states pinned across devices — each ingest is the plain
                   blocked scan (identical executable to the unsharded
                   path, so sharding costs nothing on a host CPU), and
                   consecutive batches hit different states/devices so
                   async dispatch can overlap them.

    ``auto``: CPU backend -> ``pipeline`` (a host pays shard_map's
    per-call SPMD launch without an accelerator's gain, and vmap's lane
    overhead without its launch amortization); otherwise ``shard_map``
    when more than one device can take a whole shard, else ``vmap``.
    """
    if placement not in PLACEMENTS:
        raise ValueError(
            f"placement must be one of {PLACEMENTS}, got {placement!r}"
        )
    if placement != "auto":
        return placement
    if num_shards <= 1:
        return "vmap"
    if jax.default_backend() == "cpu":
        return "pipeline"
    return (
        "shard_map" if mesh_device_count(num_shards) > 1 else "vmap"
    )


def mesh_device_count(num_shards: int, n_devices: Optional[int] = None) -> int:
    """Largest device count <= n_devices that divides ``num_shards`` (each
    device must own an equal, whole number of shard states)."""
    if n_devices is None:
        n_devices = jax.device_count()
    nd = max(1, min(int(n_devices), int(num_shards)))
    while num_shards % nd:
        nd -= 1
    return nd


@functools.lru_cache(maxsize=None)
def _sharded_mapped_fn(nd: int, spec: MatroidSpec, k: int, tau: int,
                       variant: str, eps: float, c_const: int,
                       block_size: int, step_impl: str, donate: bool):
    """jit(shard_map(vmap(scan))) over a 1-D ``shards`` mesh of nd devices,
    cached per (mesh size, scan statics). Device list is process-stable, so
    caching on nd alone is sound."""
    from jax.sharding import PartitionSpec as P

    from ..launch.mesh import make_mesh

    mesh = make_mesh((nd,), ("shards",), devices=jax.devices()[:nd])
    psh = P("shards")

    def local(sts, p, c, v, s, caps_arr):
        def one(st, p1, c1, v1, s1):
            return _ingest_core(
                st, p1, c1, v1, s1, spec, caps_arr, k, tau,
                variant, eps, c_const, block_size, step_impl,
            )

        return jax.vmap(one)(sts, p, c, v, s)

    mapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(psh, psh, psh, psh, psh, P()),
        out_specs=psh,
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def ingest_batch_sharded_mapped(
    sts: StreamState,  # stacked: every leaf has leading shard axis S
    points: jnp.ndarray,  # (S, m, d)
    cats: jnp.ndarray,  # (S, m, gamma)
    valid: jnp.ndarray,  # (S, m)
    src: jnp.ndarray,  # (S, m) global stream indices
    spec: MatroidSpec,
    caps: Optional[jnp.ndarray],
    k: int,
    tau: int,
    *,
    variant: str = "radius",
    eps: float = 0.5,
    c_const: int = 32,
    block_size: int = 128,
    step_impl: str = "branchless",
    donate: bool = False,
) -> StreamState:
    """``shard_map`` drive of sharded ingestion: the S shard states are
    partitioned across a 1-D mesh of min(devices, S) devices (largest count
    dividing S) and each device runs its local shard group as an ordinary
    program — real branches, no select-both-branches tax, true multi-device
    parallelism. Per-shard results are bit-identical to
    ``ingest_batch_sharded`` (it is the same ``_ingest_core`` under a
    different drive); on a single device this degenerates to the vmap path
    plus shard_map dispatch overhead. ``donate=True`` consumes ``sts``
    (serving hot path: the caller reassigns its state every call)."""
    S = points.shape[0]
    caps_arr = caps if caps is not None else jnp.zeros((1,), jnp.int32)
    nd = mesh_device_count(S)
    fn = _sharded_mapped_fn(
        nd, spec, k, tau, variant, float(eps), int(c_const),
        int(block_size), step_impl, bool(donate),
    )
    return fn(sts, points, cats, valid.astype(bool), src, caps_arr)


def stream_coreset(
    points: jnp.ndarray,  # (n, d) metric-normalized stream order
    cats: jnp.ndarray,  # (n, gamma)
    valid: jnp.ndarray,  # (n,)
    spec: MatroidSpec,
    caps: Optional[jnp.ndarray],
    k: int,
    tau: int,
    *,
    slot_cap: Optional[int] = None,
    variant: str = "radius",  # "radius" (§5.2 tau-controlled) | "diameter" (Alg. 2)
    eps: float = 0.5,
    c_const: int = 32,
    block_size: int = 1,
    step_impl: str = "branchless",
) -> tuple[Coreset, StreamState]:
    """One-pass streaming coreset: init + single ingest_batch + snapshot.

    Defaults to the per-point scan: a one-shot offline pass pays the blocked
    graph's larger compile without amortizing it over repeated calls (the
    serving layer, which does amortize, opts into ``block_size=128``).
    """
    n, d = points.shape
    gamma = cats.shape[1]
    st0 = init_stream_state(d, gamma, spec, k, tau, slot_cap=slot_cap)
    st = ingest_batch(
        st0, points, cats, valid, spec, caps, k, tau,
        variant=variant, eps=eps, c_const=c_const, block_size=block_size,
        step_impl=step_impl,
    )
    return snapshot_coreset(st), st


def stream_coreset_host(
    points: np.ndarray,
    cats: Optional[np.ndarray],
    matroid,
    k: int,
    tau: int,
) -> np.ndarray:
    """Host-loop streaming for general matroids (oracle-based HANDLE).

    HANDLE 'other' case of Alg. 2: always add; if D_z gains an independent
    subset of size k, shrink to it. Returns selected indices.
    """
    n, d = points.shape
    R = None
    centers: list[int] = []
    delegates: dict[int, list[int]] = {}

    def dist(i, j):
        return float(np.linalg.norm(points[i] - points[j]))

    for i in range(n):
        if len(centers) < 2:
            centers.append(i)
            delegates[i] = [i]
            if len(centers) == 2:
                R = dist(centers[0], centers[1]) / 2.0 or 1e-30
            continue
        dmin, z = min((dist(i, c), c) for c in centers)
        if dmin > 2.0 * R:
            centers.append(i)
            delegates[i] = [i]
        else:
            dz = delegates[z]
            sub = matroid.greedy_independent(dz, k)
            if len(sub) < k:
                dz.append(i)
                sub2 = matroid.greedy_independent(dz, k)
                if len(sub2) == k:
                    delegates[z] = sub2
        while len(centers) > tau:
            R *= 2.0
            kept: list[int] = []
            for c in centers:
                if all(dist(c, c2) > R for c2 in kept):
                    kept.append(c)
            dropped = [c for c in centers if c not in kept]
            centers = kept
            for c in dropped:
                for x in delegates.pop(c):
                    dmin, z = min((dist(x, c2), c2) for c2 in centers)
                    dz = delegates[z]
                    sub = matroid.greedy_independent(dz, k)
                    if len(sub) < k:
                        dz.append(x)
                        sub2 = matroid.greedy_independent(dz, k)
                        if len(sub2) == k:
                            delegates[z] = sub2
    out = sorted({x for dz in delegates.values() for x in dz})
    return np.asarray(out, np.int64)
