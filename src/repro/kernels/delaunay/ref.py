"""Pure-jnp Bowyer-Watson insertion core + the jitted, vmapped triangulator.

One chunk+halo point set per row, fixed shapes throughout so a whole
halo round vmaps into a single device dispatch:

* the ``d+1`` super-simplex vertices live at indices ``N..N+d`` (``N``
  the padded point capacity); padding slots ``cnt..N`` are never
  inserted, so a vertex id is either a real point (``< cnt``) or super
  (``>= N``),
* simplex slots carry their vertex ids and circumcenter in one packed
  float64 row (vertex ids are tiny integers, exact in f64) and the
  *squared* circumradius from the shared Cramer predicate
  (:mod:`.predicates`) in a separate ``rr`` array — the in-sphere test
  is a gather-free ``d2 < rr`` scan, and a dead or never-used slot is
  simply ``rr == -inf`` (killing a cavity is an elementwise ``where``,
  not a scatter),
* each loop trip inserts a *group* of up to ``G`` points at once: the
  candidates are the first ``G`` uninserted points, their cavities are
  scanned against the slot table in one pass, and a candidate is
  accepted when it is independent of every earlier-accepted candidate
  (cavities disjoint and not inside any of their new circumspheres) —
  independent insertions commute, so the grouped result equals the
  sequential one and the Delaunay triangulation is unique regardless;
  rejected candidates simply retry next trip.  Any *exact* incidence
  between a candidate and another candidate's new circumsphere
  (cosphericity across the group) clears ``ok`` instead of guessing,
* cavities and the accepted group's boundary facets are compacted by
  binary-searching their ``cumsum`` (XLA's CPU scatter and sort are
  serial; a few binary searches are not) into ``CAV`` slots and a
  group-wide budget of ``W = (d-1)*CAV + 2`` slots (the exact worst
  case for one ``CAV``-simplex cavity), so gather, circumsphere, and
  scatter cost track the real work of the round, not slot capacity,
* the cavity boundary is found by sort-and-count over packed facet
  keys (a facet shared by two cavity simplices is interior; seen once,
  boundary); new simplices reuse killed slots first, then append at
  ``top``.

Anything the fixed shapes cannot express — no containing simplex, a
cavity larger than ``CAV``, slot overflow, an exact in-sphere tie
(cocircular / cospherical points), a degenerate new simplex — clears
the per-row ``ok`` flag instead of producing a wrong triangulation;
the emitter treats ``not ok`` exactly like a failed certificate and
expands the halo.

The insertion loop is a ``lax.while_loop`` whose trip count is bounded
by the emitter-derived point count (at least one candidate — the
earliest — is accepted per trip); under ``vmap`` all rows advance in
lockstep until the longest row finishes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .predicates import circumsphere

# super-simplex vertex directions (scaled by the row's extent): an
# equilateral triangle / regular tetrahedron whose insphere covers the
# point bounding box with orders of magnitude to spare
_SUPER_UNIT = {
    2: ((0.0, 2.0), (-1.7320508075688772, -1.0), (1.7320508075688772, -1.0)),
    3: ((1.0, 1.0, 1.0), (1.0, -1.0, -1.0), (-1.0, 1.0, -1.0),
        (-1.0, -1.0, 1.0)),
}
_SUPER_SCALE = 512.0

# facet k of a simplex = all vertices but k
_FACET_IDX = {
    2: ((1, 2), (0, 2), (0, 1)),
    3: ((1, 2, 3), (0, 2, 3), (0, 1, 3), (0, 1, 2)),
}

# candidates considered per loop trip (see module docstring)
GROUP = 4


def boundary_capacity(cavity: int, dim: int) -> int:
    """Max boundary facets of a connected cavity of ``cavity`` simplices:
    ``(d+1)*cavity`` facet slots minus the ``2*(cavity-1)`` interior
    pairings."""
    return (dim - 1) * cavity + 2


def triangulate(pts, cnt, *, dim: int, num_simplices: int, cavity: int,
                group: int = GROUP):
    """Incremental Delaunay triangulation of one padded point row.

    pts: [N, d] float64 (slots >= cnt ignored), cnt: scalar int.
    Returns ``(simp [S, d+1] int32, alive [S] bool, ok bool)``: the
    alive slots triangulate points+super; rows with any vertex >= N are
    super-incident (the hull certificate reads them, the edge phase
    drops them).  ``ok`` is False when the fixed capacities or general
    position were violated — the caller must expand and retry.
    """
    N = pts.shape[0]
    S, CAV, G = num_simplices, cavity, group
    F = CAV * (dim + 1)
    W = boundary_capacity(CAV, dim)   # group-wide new-simplex budget
    UC = 3 * CAV                  # union-cavity window for a whole group
    fidx = jnp.asarray(_FACET_IDX[dim], jnp.int32)
    V = N + dim + 1

    valid = jnp.arange(N, dtype=jnp.int32) < cnt
    lo = jnp.min(jnp.where(valid[:, None], pts, jnp.inf), axis=0)
    hi = jnp.max(jnp.where(valid[:, None], pts, -jnp.inf), axis=0)
    lo = jnp.where(jnp.isfinite(lo), lo, 0.0)
    hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    center = 0.5 * (lo + hi)
    extent = 0.5 * jnp.max(hi - lo) + 1.0
    sup = center[None, :] + _SUPER_SCALE * extent * jnp.asarray(
        _SUPER_UNIT[dim], pts.dtype)
    work = jnp.concatenate([pts, sup], axis=0)          # [V, d]

    # packed slot row = d+1 vertex ids (exact small ints in f64) + the
    # d circumcenter coordinates; rr = squared radius, -inf == dead
    c0, r20, nd0 = circumsphere(sup)
    packed = jnp.zeros((S, 2 * dim + 1), pts.dtype)
    packed = packed.at[0].set(jnp.concatenate(
        [jnp.arange(dim + 1, dtype=pts.dtype) + N, c0]))
    rr = jnp.full(S, -jnp.inf, pts.dtype)
    rr = rr.at[0].set(jnp.where(nd0, r20, jnp.inf))

    # facet keys fit int32 for every realistic bucket size; int64 is the
    # safety net for enormous rows
    ktype = jnp.int32 if V ** dim + F < 2 ** 31 else jnp.int64
    # narrow counters keep the per-trip cumsums cheap; widen when the
    # slot table could overflow int16
    cdt = jnp.int16 if max(S, N) < 2 ** 15 else jnp.int32

    def body(state):
        nins, ins, packed, rr, top, ok = state
        # candidates: G uninserted points spread evenly across the
        # remaining ranks — points arrive cell-ordered, so consecutive
        # ranks are spatial neighbours with colliding cavities, while
        # strided ranks are mostly independent and the whole group lands
        icum = jnp.cumsum((valid & ~ins).astype(cdt))
        rem = (cnt - nins).astype(jnp.int32)
        stride = jnp.maximum(rem // G, 1)
        ranks = jnp.arange(G, dtype=jnp.int32) * stride
        cand = jnp.searchsorted(
            icum, (ranks + 1).astype(icum.dtype)).astype(jnp.int32)
        cm = ranks < rem
        p = work[jnp.clip(cand, 0, V - 1)]               # [G, d]

        # one in-sphere scan of the slot table for the whole group; d2
        # expands to |cc|^2 - 2 cc.p + |p|^2 so the G columns come from
        # a single [S,d]x[d,G] matmul instead of G elementwise passes
        cc = packed[:, dim + 1:]
        d2 = (jnp.sum(cc * cc, axis=1)[:, None]
              - 2.0 * (cc @ p.T)
              + jnp.sum(p * p, axis=1)[None, :])
        bad = (d2 < rr[:, None]) & cm[None, :]           # [S, G]
        tie = (d2 == rr[:, None]) & cm[None, :]
        # compact the slots bad for ANY candidate (the union cavity)
        # into UC entries in one pass — cumsum is nondecreasing, so the
        # j-th set slot sits at the first index where the running count
        # reaches j+1 — then build each candidate's cavity inside that
        # small window
        bany = jnp.any(bad, axis=1)
        ucum = jnp.cumsum(bany.astype(cdt))
        nu = ucum[-1].astype(jnp.int32)
        uni = jnp.searchsorted(
            ucum, jnp.arange(UC, dtype=cdt) + 1).astype(jnp.int32)
        badu = bad[jnp.clip(uni, 0, S - 1)] \
            & (jnp.arange(UC, dtype=jnp.int32) < nu)[:, None]  # [UC, G]
        cumu = jnp.cumsum(badu.astype(cdt), axis=0)
        nb = cumu[-1].astype(jnp.int32)                  # [G]
        cav1 = jnp.arange(CAV, dtype=cdt) + 1
        locidx = jax.vmap(
            lambda c: jnp.searchsorted(c, cav1),
            in_axes=1)(cumu).astype(jnp.int32)           # [G, CAV]
        badidx = jnp.where(locidx < UC,
                           uni[jnp.clip(locidx, 0, UC - 1)], S)
        cmask = jnp.arange(CAV, dtype=jnp.int32)[None, :] < nb[:, None]
        cav = packed[jnp.clip(badidx, 0, S - 1), :dim + 1].astype(jnp.int32)
        facets = jnp.sort(cav[:, :, fidx], axis=-1)      # [G, CAV, d+1, d]
        ffl = facets.reshape(G, F, dim)
        ff = ffl.astype(ktype)
        fm = jnp.repeat(cmask, dim + 1, axis=1)          # [G, F]
        key = ff[:, :, 0]
        for k in range(1, dim):
            key = key * V + ff[:, :, k]
        # masked rows get unique sentinel keys so they never pair with
        # (or shadow) a real facet in the occurrence count
        key = jnp.where(fm, key,
                        ktype(V) ** dim + jnp.arange(F, dtype=ktype)[None, :])
        sk = jnp.sort(key, axis=1)
        # a key is a boundary facet iff it occurs exactly once: the
        # entry after its first sorted occurrence differs
        left = jax.vmap(functools.partial(jnp.searchsorted, side="left"))(
            sk, key)
        nxt = jnp.take_along_axis(sk, jnp.clip(left + 1, 0, F - 1), axis=1)
        bnd = fm & jnp.where(left + 1 < F, nxt != key, True)
        bcum = jnp.cumsum(bnd.astype(cdt), axis=1)
        nnew = bcum[:, -1].astype(jnp.int32)             # [G]

        # stage-1 acceptance: candidate j survives if no earlier
        # survivor's cavity overlaps its cavity (independent insertions
        # commute) and the group's new-simplex budget W holds
        ov = jnp.einsum("uj,ul->jl", badu.astype(jnp.int32),
                        badu.astype(jnp.int32)) > 0      # [G, G]
        accs = [cm[0]]
        newsum = jnp.where(cm[0], nnew[0], 0)
        for j in range(1, G):
            prev = jnp.stack(accs)
            take = (cm[j] & ~jnp.any(prev & ov[:j, j])
                    & (newsum + nnew[j] <= W))
            accs.append(take)
            newsum = newsum + jnp.where(take, nnew[j], 0)
        acc = jnp.stack(accs)                            # [G]

        # compact the survivors' boundary facets to exact width W, THEN
        # gather vertices and run the circumsphere — per-row scatter and
        # gather overhead tracks the real work, not G*F slot capacity
        wflat = (acc[:, None] & bnd).reshape(G * F)
        wcum = jnp.cumsum(wflat.astype(cdt))
        nw = wcum[-1].astype(jnp.int32)
        wsel = jnp.searchsorted(
            wcum, jnp.arange(W, dtype=cdt) + 1).astype(jnp.int32)
        wm = jnp.arange(W, dtype=jnp.int32) < nw
        wsafe = jnp.clip(wsel, 0, G * F - 1)
        wowner = wsafe // F                              # candidate index
        lpos = (jnp.take(bcum.reshape(G * F), wsafe) - 1).astype(jnp.int32)
        wf = ffl.reshape(G * F, dim)[wsafe]              # [W, d]
        wnew = jnp.concatenate(
            [wf, cand[wowner][:, None]], axis=1)         # [W, d+1]
        wctr, wr2, wnok = circumsphere(work[wnew])       # [W, ...]

        # stage-2 acceptance: demote candidate j if it lies inside (or
        # exactly on — cosphericity clears ok) a new circumsphere of an
        # earlier survivor; removals only weaken stage-1 constraints,
        # so the greedy chain stays valid
        pw = jnp.sum((wctr[:, None, :] - p[None, :, :]) ** 2, axis=2)
        oh = ((wowner[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :])
              & wm[:, None]).astype(jnp.int32)           # [W, G] owner 1-hot
        hg = (oh.T @ (pw < wr2[:, None]).astype(jnp.int32)) > 0
        tg = (oh.T @ (pw == wr2[:, None]).astype(jnp.int32)) > 0
        faccs = [acc[0]]
        for j in range(1, G):
            prev = jnp.stack(faccs)
            faccs.append(acc[j] & ~jnp.any(prev & hg[:j, j]))
        facc = jnp.stack(faccs)                          # [G]

        # slot allocation: each survivor's cavity reuses its own killed
        # slots first, then appends to a per-candidate range past top
        a = jnp.where(facc, jnp.maximum(nnew - nb, 0), 0)
        aoff = (jnp.cumsum(a) - a).astype(jnp.int32)
        fmask = wm & facc[wowner]
        nb_o = nb[wowner]
        slots = jnp.where(
            fmask,
            jnp.where(lpos < nb_o,
                      badidx[wowner, jnp.clip(lpos, 0, CAV - 1)],
                      top + aoff[wowner] + lpos - nb_o),
            S + jnp.arange(W, dtype=jnp.int32))  # OOB == dropped
        killed = jnp.any(bad & facc[None, :], axis=1)
        rr = jnp.where(killed, -jnp.inf, rr)  # kill cavities, elementwise
        packed = packed.at[slots].set(
            jnp.concatenate([wnew.astype(pts.dtype), wctr], axis=1),
            mode="drop", unique_indices=True)
        rr = rr.at[slots].set(jnp.where(wnok, wr2, jnp.inf), mode="drop",
                              unique_indices=True)
        top = top + jnp.sum(a).astype(top.dtype)
        ins = ins.at[cand].set(facc, mode="drop", unique_indices=True)
        nins = nins + jnp.sum(facc).astype(nins.dtype)
        gi = jnp.arange(G, dtype=jnp.int32)
        offdiag = gi[:, None] != gi[None, :]
        ok = (ok
              & (nu <= UC)
              & jnp.all(jnp.where(facc,
                                  (nb > 0) & (nb <= CAV) & (nnew <= W),
                                  True))
              & ~jnp.any(tie)
              & ~jnp.any(fmask & ~wnok)
              & ~jnp.any(tg & facc[:, None] & facc[None, :] & offdiag)
              & (top <= S))
        return nins, ins, packed, rr, top, ok

    state = jax.lax.while_loop(
        lambda s: s[0] < cnt, body,
        (jnp.int32(0), jnp.zeros(N, bool), packed, rr, jnp.int32(1),
         jnp.bool_(True)))
    _, _, packed, rr, top, ok = state
    simp = packed[:, :dim + 1].astype(jnp.int32)
    alive = rr > -jnp.inf
    return simp, alive, ok


@functools.partial(jax.jit,
                   static_argnames=("dim", "num_simplices", "cavity",
                                    "group"))
def delaunay_ref(pts, cnt, *, dim: int, num_simplices: int, cavity: int,
                 group: int = GROUP):
    """RDG's triangulator on every backend: vmap of :func:`triangulate`
    over batch rows.
    pts: [B, N, d] float64, cnt: [B] int32."""
    core = functools.partial(triangulate, dim=dim,
                             num_simplices=num_simplices, cavity=cavity,
                             group=group)
    return jax.vmap(core)(pts, cnt)
