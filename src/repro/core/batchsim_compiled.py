"""Compiled lock-step batch core: the numpy pass as one jitted XLA loop.

:mod:`repro.core.batchsim` advances every lane's event frontier with masked
numpy array ops — correct and bit-exact, but interpreter-bound: each event
touches ~30 scalars across ~60 numpy dispatches, so at GA widths the pass
*loses* to the per-solution Python loop (``BENCH_simspeed.json`` →
``batch_speedup`` ≈ 0.49). This module ports the identical pass into a
single ``jax.lax.while_loop`` compiled by XLA: one compiled program per
shape bucket, zero Python dispatch per event, every handler a masked
full-width update exactly mirroring the numpy op sequence.

Tolerance contract
------------------
The compiled tier is **not** contractually bit-exact; it is exact on
*inputs* and bounded on *arithmetic*:

* every RNG-derived quantity is precomputed host-side with the scalar
  engines' exact expressions — arrival tables via ``draw_arrivals``, noise
  z-draws via ``random.Random(seed).gauss`` with the multiplier computed by
  ``math.exp`` (per ``(draw index, pid)``, gathered in-loop), straggler
  multipliers via the one-draw-per-delivery ``random.Random`` stream with
  the scalar Pareto expression — so the compiled loop consumes bit-identical
  event inputs;
* the in-loop float arithmetic uses the same operation order as the scalar
  engines, but XLA owns the instruction selection (e.g. FMA contraction),
  so results carry a documented bounded tolerance instead of a bit-parity
  promise: :data:`COMPILED_REL_TOL` relative / :data:`COMPILED_ABS_TOL`
  absolute per reported float. In practice the observed diff on the golden
  traces and the differential suite is 0.0 on x86-64 (XLA CPU emits IEEE
  double ops for this graph); the tolerance is the contract, the zero is
  the measurement. The numpy tier remains the bit-exact parity oracle.

Fallbacks (counted and logged by :func:`repro.core.batchsim.run_batch`)
----------------------------------------------------------------------
* ``collect_tasks=True`` — task-trace collection is python-side by design,
  so ``run_batch`` sends such batches to numpy without asking this core;
* ready-queue bound — the ready queues are keys in a table with one entry
  per ``(lane, request, subgraph)``; a lane set whose requests × subgraphs
  exceed :data:`QUEUE_PAIRS_MAX`, or whose priorities do not fit the
  key's ``int32`` word, runs on the numpy tier, whose queues grow without
  bound (reason ``"queue-bound"``);
* iteration-cap guard — a generous host-computed event bound; hitting it
  (impossible by construction, like the numpy z-table bound) falls back
  rather than hanging inside XLA (reason ``"itercap"``).

Each of the last two returns ``None`` with the reason in ``last_stats``;
``run_batch`` counts it in ``batchsim.compiled_fallbacks`` and logs a
warning, so a run that was meant for the device never silently stays on
the host. JAX itself is required: a failed import is an error.

Ready queues: a key table instead of scanned slots
--------------------------------------------------
The numpy tier keeps per-``(lane, pid)`` slot arrays and scans them
(argmin over packed ``(class, priority, release_seq)`` keys) on every pop,
and grows them when they fill. The compiled core uses a structural
property instead: within a lane every ``(request, subgraph)`` pair is
released exactly once, so a ready queue needs no slots, only one key per
pair, and the loop already holds a ``(W, R, S)`` ``int32`` table indexed
by that pair: ``pend``, each task's count of unfinished predecessors. A
task waiting for its worker keeps its key there, ``priority << SEQ_BITS
| release_seq`` (the numpy tier's order without its class: dispatch
tokens carry no payload and live in a per-lane counter, popped first),
stored as ``key + INT32_MIN`` so that every queued entry is negative and
every pending count is not. All releases of one event belong to one
request (the arriving request's roots, or the completed subgraph's
successors, which share its request), so the event's new counts and keys
make one row, written with one masked select. A pop is one masked min
over the entries whose subgraph runs on the worker; keys are unique
within a lane, so the entry equal to a negative minimum is the task
popped, and a one-hot reduction gives back its ``(request, subgraph)``.
Neither needs a scatter, a gather or head/tail counters. A separate key
table compiled to 5-6 more fusions of the body on v5e.

``float64`` everywhere: calls run under ``jax.enable_x64(True)`` so
the repo's global default (float32, required by the kernel/model stacks)
is untouched.

One-hot reads and writes
------------------------
The loop's carry and tables are small per-lane arrays, so on TPU an
iteration costs its number of kernels, not its arithmetic: every gather,
scatter and dynamic-update-slice is a kernel of its own, and each 64-bit
array is a pair of 32-bit ones. Compiled for v5e at GA width (80 lanes),
indexed reads and column writes gave the body 201 fusions (69 of them
gathers) and 24 dynamic-update-slices when clean, and 240-250 fusions and
40 dynamic-update-slices with noise, dispatch load and faults. Every read
and write over a small static axis is therefore a one-hot select or
masked reduction (:func:`oh_get`), which fuses with its neighbours: 156,
174 and 182 fusions, none of them a dynamic-update-slice. The ready
queues as keys in ``pend`` then took the scatters and the gathers of FIFO
rings out: 110, 129 and 137 fusions. A true gather is kept only for axes
longer than :data:`ONE_HOT_MAX` (the noise and straggler tables).
``tests/test_chip_compile.py`` holds the body to this.
"""
from __future__ import annotations

import math
import operator
import random
from collections import Counter
from dataclasses import dataclass
from functools import partial, reduce
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..spans import span
from .arrivals import arrival_horizon, draw_arrivals
from .processors import Processor

#: Documented tolerance of the compiled tier relative to the bit-exact
#: numpy tier, per reported float (makespans, busy times, timestamps).
COMPILED_REL_TOL = 1e-9
COMPILED_ABS_TOL = 1e-12

#: Largest requests × subgraphs of one lane's request group that the
#: compiled tier accepts: the ready queues keep one key per
#: ``(request, subgraph)`` pair, in ``pend``, so this bounds that table.
#: A lane set above it runs on the numpy tier (its queues grow without
#: bound).
QUEUE_PAIRS_MAX = 4096

#: Bits of a ready-queue key below the priority: the lane's release
#: counter, which counts every task and dispatch token it releases, at
#: most ``2 × QUEUE_PAIRS_MAX``.
SEQ_BITS = (2 * QUEUE_PAIRS_MAX).bit_length()

#: Largest per-lane axis that the lock-step loop reads as a one-hot masked
#: reduction; a longer axis keeps a true gather. On TPU every gather is a
#: kernel of its own (two for a 64-bit table, which the chip holds as a
#: pair of 32-bit words): ~0.4 µs each at 16 lanes on one v5e, while a
#: one-hot read fuses with its neighbours (~0.2 µs per reduction) but
#: touches W × axis elements (× K for the delivery slots). The small
#: static axes (workers, groups, subgraphs, requests) lie below the
#: limit, the noise and straggler tables (one entry per draw) above.
#: On one v5e a limit of 16, which reads the subgraph axis with gathers,
#: cost 10-16% more per iteration, and one of 64, which reads the request
#: axis with gathers, was within 2% of this one.
ONE_HOT_MAX = 512

_BIGSEQ = np.int64(1) << 62


def oh_get(arr, *idx):
    """``arr[lane, *idx]`` for every lane, as the lock-step loop reads it.

    ``arr`` is ``(W, d1, ..., dk, *rest)`` and each of the ``k`` indices
    is ``(W,)`` or ``(W, K)``; the result is ``idx.shape + rest``. The
    indexed axes, ``d1 × ... × dk`` entries together, are read with a
    one-hot mask up to :data:`ONE_HOT_MAX` entries and with a gather
    above. Every index must be in range: a gather clamps one that is not,
    a one-hot read returns zero. The read is exact and keeps
    ``arr``'s dtype: booleans reduce with ``any``, integers with a ``sum``
    in their own dtype (not widened under x64), floats with a ``sum`` of
    the one selected term and ``+0.0`` for every other, which is exact
    (``inf`` included) up to the sign of a zero.
    """
    import jax.numpy as jnp

    W, k = arr.shape[0], len(idx)
    dims, rest = arr.shape[1:1 + k], arr.shape[1 + k:]
    idx = [jnp.asarray(i) for i in idx]
    shape = jnp.broadcast_shapes(*(i.shape for i in idx))
    lead = (1,) * (len(shape) - 1)
    if math.prod(dims) > ONE_HOT_MAX:
        return arr[(jnp.arange(W).reshape((W,) + lead), *idx)]
    # one mask over the indexed axes, an outer product of one per axis:
    # no reshape of the table, which on TPU would relayout it
    masks = []
    for a, (i, d) in enumerate(zip(idx, dims)):
        pos = [1] * k
        pos[a] = d
        masks.append(jnp.broadcast_to(i, shape).reshape(shape + (1,) * k)
                     == jnp.arange(d).reshape(pos))
    hot = reduce(operator.and_, masks)
    hot = hot.reshape(hot.shape + (1,) * len(rest))
    a = arr.reshape((W,) + lead + dims + rest)
    ax = tuple(range(len(shape), len(shape) + k))
    if a.dtype == jnp.bool_:
        return jnp.any(hot & a, axis=ax)
    if jnp.issubdtype(a.dtype, jnp.floating):
        return jnp.sum(jnp.where(hot, a, 0.0), axis=ax)
    return jnp.sum(jnp.where(hot, a, 0), axis=ax, dtype=a.dtype)


def _bucket(n: int, lo: int = 1) -> int:
    """Round ``n`` up to a power of two (≥ ``lo``) — shape bucketing keeps
    the jit cache small across GA generations with jittering widths."""
    v = max(int(n), lo)
    return 1 << (v - 1).bit_length()


def _advance_factory() -> object:
    """Build the jitted lock-step advance once per process."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @partial(jax.jit, static_argnums=0)
    def advance(flags, tab):
        G, P, any_noise, any_fault, any_strag, any_dispatch = flags
        arrtab = tab["arrtab"]            # (W, G, NR)
        W, _, NR = arrtab.shape
        S = tab["exec_v"].shape[1]
        R = G * NR
        C = G + P + 1
        K = P + 1
        jmax = tab["roots"].shape[2]
        dmax = tab["succ_pad"].shape[2]
        horizon = tab["horizon"]
        nr = tab["nr"]
        # tables read at the same index, side by side: one read for each
        # group, not one per table
        place = jnp.stack([tab["proc_of"], tab["prio_of"]], -1)
        cost = jnp.stack([tab["exec_v"], tab["total_v"], tab["quant_v"],
                          tab["comm_v"]], -1)
        succ = jnp.concatenate([tab["succ_cnt"][..., None],
                                tab["succ_pad"]], -1)
        roots = jnp.concatenate([tab["roots_n"][..., None], tab["roots"]],
                                -1)
        dep_cnt = tab["dep_cnt"]
        overlap = tab["overlap"]
        dispatch_ov = tab["dispatch_ov"]
        dispatch_pid = tab["dispatch_pid"]
        dispatch_known = tab["dispatch_known"]
        noisy = tab["noisy"]
        sigma_pos = tab["sigma_pos"]      # (W, P) bool: sigma > 0
        emult = tab["emult"]              # (W, ZC, P) math.exp multipliers
        faulted = tab["faulted"]
        strag_on = tab["strag_on"]
        strag_tab = tab["strag_tab"]      # (W, FC)
        thr_pid = tab["thr_pid"]          # (W, T)
        thr_t0, thr_t1, thr_fac = tab["thr_t0"], tab["thr_t1"], tab["thr_fac"]
        drop_pid = tab["drop_pid"]        # (W, D)
        drop_t0, drop_t1 = tab["drop_t0"], tab["drop_t1"]
        idle0 = tab["idle0"]              # (P,) bool
        itercap = tab["itercap"]
        ZC = emult.shape[1]
        FC = strag_tab.shape[1]
        T = thr_pid.shape[1]
        D = drop_pid.shape[1]
        i64 = jnp.int64
        BIGSEQ = i64(_BIGSEQ)
        INF = jnp.float64(jnp.inf)
        M21 = i64((1 << 21) - 1)
        QBASE = jnp.int32(np.iinfo(np.int32).min)
        proc_of = tab["proc_of"]
        # (request, subgraph) as one position in the pending table
        RS = jnp.arange(R * S, dtype=jnp.int32).reshape(R, S)

        # --- one-hot reads and writes -----------------------------------
        # On TPU an iteration costs its number of kernels, and every
        # gather, scatter and dynamic-update-slice is one of its own (two
        # for a 64-bit array): see the module docstring. Every read and
        # write over a small static axis (frontier columns C, workers P,
        # ring slots K, groups G, requests R, subgraphs S) is a one-hot
        # select or masked reduction that fuses with its neighbours
        # (:func:`oh_get`, ``oh_set``). Only axes longer than
        # :data:`ONE_HOT_MAX` keep a gather (the noise and straggler
        # tables). The ready queues are keys in ``pend`` (module
        # docstring): a push is a masked select into one request's row, a
        # pop a masked min over the table, so neither is a scatter or a
        # gather.
        def oh(m, col, width):
            return m[:, None] & (jnp.asarray(col)[..., None]
                                 == jnp.arange(width))

        def oh_set(arr, m, col, val):
            o = oh(m, col, arr.shape[1])
            v = val[:, None] if getattr(val, "ndim", 0) else val
            return jnp.where(o, v, arr)

        # --- masked primitive updates ------------------------------------
        def append_deliver(st, m, pid, g, rr, t):
            st["idle"] = st["idle"] & ~oh(m, pid, P)
            pos = st["del_n"]
            # ring payload (pid, g, rr) packed into one word: one update
            pack = ((pid + 1) << 42) | ((g + 1) << 21) | (rr + 1)
            st["del_pack"] = oh_set(st["del_pack"], m, pos, pack)
            we = m & (pos == 0)
            st["times"] = oh_set(st["times"], we, C - 1, t)
            st["seqs"] = oh_set(st["seqs"], we, C - 1, st["seq"])
            st["del_n"] = st["del_n"] + m
            st["seq"] = st["seq"] + m
            return st

        def placements(gs):
            """(worker, priority) of each subgraph in ``gs`` (W, J): every
            release of an event at once, ahead of the releases."""
            pl = oh_get(place, jnp.clip(gs, 0, S - 1))
            return pl[..., 0], pl[..., 1]

        def release(st, row, m, g, rr, t, pid, prio):
            """Reference ``release()``: dispatch token, then the task
            (subgraph ``g`` on worker ``pid`` at priority ``prio``).

            Tokens carry no payload and only ever queue on the lane's
            single ``dispatch_pid``, so the token queue is a per-lane
            counter. A task whose worker is busy gets its key in ``row``,
            the ``pend`` row of request ``rr`` that the body writes once
            every release of the event is in it."""
            neg1 = jnp.full((W,), -1, i64)
            if any_dispatch:
                dm = m & dispatch_known
                st["rel_seq"] = st["rel_seq"] + dm
                d_idle = oh_get(st["idle"], dispatch_pid)
                st = append_deliver(st, dm & d_idle, dispatch_pid,
                                    neg1, neg1, t)
                st["tok"] = st["tok"] + (dm & ~d_idle)
            st["rel_seq"] = st["rel_seq"] + m
            is_idle = oh_get(st["idle"], pid)
            st = append_deliver(st, m & is_idle, pid, g, rr, t)
            q = m & ~is_idle
            st["queued"] = st["queued"] + q
            key = ((prio << SEQ_BITS) | st["rel_seq"]).astype(jnp.int32)
            return st, oh_set(row, q, g, key + QBASE)

        def pull_next(st, m, pid, t):
            """Pop the earliest-keyed entry: queued dispatch tokens first
            (class 0), else the least key among the queued tasks whose
            subgraph runs on ``pid``. Keys are unique within a lane, so
            the entry equal to that minimum is the one popped."""
            if any_dispatch:
                tok_has = m & (pid == dispatch_pid) & (st["tok"] > 0)
                st["tok"] = st["tok"] - tok_has
            else:
                tok_has = jnp.zeros((W,), bool)
            pend = st["pend"]
            mine = (proc_of == pid[:, None])[:, None, :]
            kmin = jnp.min(jnp.where(mine, pend, 0), axis=(1, 2))
            task_has = m & ~tok_has & (kmin < 0)
            hot = (pend == kmin[:, None, None]) & task_has[:, None, None]
            at = jnp.sum(jnp.where(hot, RS, 0), axis=(1, 2),
                         dtype=jnp.int32).astype(i64)
            g = jnp.where(tok_has, -1, at % S)
            rr = jnp.where(tok_has, -1, at // S)
            st["pend"] = jnp.where(hot, 0, pend)
            has = tok_has | task_has
            st = append_deliver(st, has, pid, g, rr, t)
            st["idle"] = st["idle"] | oh(m & ~has, pid, P)
            return st

        def cond(st):
            tmin = jnp.min(st["times"], axis=1)
            return (st["it"] < itercap) & jnp.any(tmin <= horizon)

        def body(st):
            tmin = jnp.min(st["times"], axis=1)
            smask = jnp.where(st["times"] == tmin[:, None], st["seqs"],
                              BIGSEQ)
            ci = jnp.argmin(smask, axis=1)
            act = tmin <= horizon
            st["events"] = st["events"] + act.astype(jnp.int32)
            now = tmin
            t = now

            # -- request arrivals -------------------------------------
            mA = act & (ci < G)
            gid = jnp.where(mA, ci, 0)
            rid = oh_get(st["src_rid"], gid)
            arow = oh_get(arrtab, gid)                   # (W, NR)
            a0 = arow[:, 0]
            defer = mA & (rid == 0) & (a0 > t)
            st["times"] = oh_set(st["times"], defer, gid, t + (a0 - t))
            st["seqs"] = oh_set(st["seqs"], defer, gid, st["seq"])
            st["seq"] = st["seq"] + defer
            arr_m = mA & ~defer
            rr = gid * NR + rid
            o_a = oh(arr_m, rr, R)
            st["arrival"] = jnp.where(o_a, t[:, None], st["arrival"])
            rrow = oh_get(roots, gid)                    # (W, 1 + jmax)
            pids, prios = placements(rrow[:, 1:])
            row = dep_cnt
            for j in range(jmax):
                st, row = release(st, row, arr_m & (j < rrow[:, 0]),
                                   rrow[:, 1 + j], rr, t, pids[:, j],
                                   prios[:, j])
            rq = rr
            nrid = rid + 1
            has = arr_m & (nrid < nr)
            arr_next = oh_get(arow, jnp.minimum(nrid, NR - 1))
            st["times"] = oh_set(
                st["times"], arr_m, gid,
                jnp.where(has, t + (arr_next - t), INF))
            st["seqs"] = oh_set(st["seqs"], arr_m, gid,
                                jnp.where(has, st["seq"], BIGSEQ))
            st["seq"] = st["seq"] + has
            st["src_rid"] = oh_set(st["src_rid"], has, gid, nrid)

            # -- worker completions -----------------------------------
            mC = act & (ci >= G) & (ci < G + P)
            pid = jnp.clip(ci - G, 0, P - 1)
            g = oh_get(st["end_g"], pid)
            rr = oh_get(st["end_rr"], pid)
            real = mC & (g >= 0)
            o_r = oh(real, rr, R)
            st["done"] = st["done"] + o_r
            st["last_finish"] = jnp.where(
                o_r, jnp.maximum(st["last_finish"], t[:, None]),
                st["last_finish"])
            g_c = jnp.clip(g, 0, S - 1)
            rr_c = jnp.clip(rr, 0, R - 1)
            # the request's pending counts as one row, every successor's
            # decrement at once: successor j reads its count less the
            # decrements of successors 0..j that name the same subgraph,
            # as the scalar loop's in-order decrements would leave it
            prow = oh_get(st["pend"], rr_c)              # (W, S)
            srow = oh_get(succ, g_c)                     # (W, 1 + dmax)
            sjs = srow[:, 1:]                            # (W, dmax)
            jj = jnp.arange(dmax)
            mjs = real[:, None] & (jj < srow[:, :1])
            same = ((sjs[:, :, None] == sjs[:, None, :]) & mjs[:, None, :]
                    & (jj[:, None] >= jj[None, :]))
            pjs = oh_get(prow, sjs) - jnp.sum(same, axis=2, dtype=prow.dtype)
            dec = mjs[:, :, None] & (sjs[:, :, None] == jnp.arange(S))
            prow = prow - jnp.sum(dec, axis=1, dtype=prow.dtype)
            row = jnp.where(mA[:, None], row, prow)
            pids, prios = placements(sjs)
            for j in range(dmax):
                st, row = release(st, row, mjs[:, j] & (pjs[:, j] == 0),
                                  sjs[:, j], rr, t, pids[:, j], prios[:, j])
            # one row write for the event: the arriving request's fresh
            # counts, or the completed task's request with its successors'
            # decrements, each with the keys of its queued releases
            rq = jnp.where(mA, rq, rr_c)
            st["pend"] = jnp.where(oh(arr_m | real, rq, R)[:, :, None],
                                   row[:, None, :], st["pend"])
            st["times"] = oh_set(st["times"], mC, G + pid, INF)
            st["seqs"] = oh_set(st["seqs"], mC, G + pid, BIGSEQ)
            st["end_g"] = oh_set(st["end_g"], mC, pid, i64(-2))
            st = pull_next(st, mC, pid, t)

            # -- delivery-ring drain ----------------------------------
            # All K slots at once. This is sound because (a) every slot
            # shares the drain's single timestamp t, (b) a pid appears at
            # most once in the ring (append_deliver requires the pid idle
            # and immediately clears idle, so a second delivery for the
            # same pid cannot enter before the drain), hence the per-pid
            # and per-column writes below never collide, and (c) the only
            # slot-order-dependent state — the seq counter and the
            # zpos/fpos RNG cursors — is reproduced with exclusive prefix
            # counts over the slot axis, giving each slot the exact value
            # the scalar left-to-right drain would hand it.
            mD = act & (ci == C - 1)
            kk = jnp.arange(K)[None, :]
            mk = mD[:, None] & (kk < st["del_n"][:, None])       # (W, K)
            v = st["del_pack"]
            pidj = (v >> 42) - 1
            gj = ((v >> 21) & M21) - 1
            rrj = (v & M21) - 1
            pid_c = jnp.clip(pidj, 0, P - 1)
            gj_c = jnp.clip(gj, 0, S - 1)
            disp = mk & (gj < 0)
            realm = mk & (gj >= 0)
            tK = t[:, None]
            seq_at = st["seq"][:, None] + (jnp.cumsum(mk, axis=1) - mk)
            st["seq"] = st["seq"] + jnp.sum(mk, axis=1)
            exec_t, total, quant, comm = jnp.moveaxis(
                oh_get(cost, gj_c), -1, 0)
            cm = jnp.where(overlap[:, None], 0.0, comm)
            if any_noise:
                draw = realm & noisy[:, None] & oh_get(sigma_pos, pid_c)
                zat = st["zpos"][:, None] + (jnp.cumsum(draw, axis=1) - draw)
                mult = oh_get(emult, jnp.minimum(zat, ZC - 1), pid_c)
                st["zpos"] = st["zpos"] + jnp.sum(draw, axis=1)
                et = exec_t * mult
                # same order as the scalar loop: exec + quant + (0|comm)
                tt = et + quant + cm
                exec_t = jnp.where(draw, et, exec_t)
                total = jnp.where(draw, tt, total)
            if any_fault:
                fm = realm & faulted[:, None]
                ex_f = exec_t
                if any_strag:
                    sd = fm & strag_on[:, None]
                    fat = st["fpos"][:, None] + (jnp.cumsum(sd, axis=1) - sd)
                    sm = oh_get(strag_tab, jnp.minimum(fat, FC - 1))
                    st["fpos"] = st["fpos"] + jnp.sum(sd, axis=1)
                    ex_f = jnp.where(sd, ex_f * sm, ex_f)
                for ti in range(T):
                    match = (fm & (thr_pid[:, ti, None] == pidj)
                             & (thr_t0[:, ti, None] <= tK)
                             & (tK < thr_t1[:, ti, None]))
                    ex_f = jnp.where(match, ex_f * thr_fac[:, ti, None],
                                     ex_f)
                stall = jnp.zeros((W, K))
                found = jnp.zeros((W, K), bool)
                for di in range(D):
                    match = (fm & ~found & (drop_pid[:, di, None] == pidj)
                             & (drop_t0[:, di, None] <= tK)
                             & (tK < drop_t1[:, di, None]))
                    stall = jnp.where(match, drop_t1[:, di, None] - tK,
                                      stall)
                    found = found | match
                tt = ex_f + quant + cm
                tt = jnp.where(stall > 0.0, stall + tt, tt)
                exec_t = jnp.where(fm, ex_f, exec_t)
                total = jnp.where(fm, tt, total)
            ohr = (realm[:, :, None]
                   & (rrj[:, :, None] == jnp.arange(R)[None, None, :]))
            st["first_start"] = jnp.where(
                jnp.any(ohr, axis=1),
                jnp.minimum(st["first_start"], tK),
                st["first_start"])
            fin = realm & jnp.isfinite(total)
            ohp = ((disp | realm)[:, :, None]
                   & (pid_c[:, :, None] == jnp.arange(P)[None, None, :]))
            badd = jnp.where(disp, dispatch_ov[:, None],
                             jnp.where(fin, total, 0.0))
            st["busy"] = st["busy"] + jnp.sum(
                jnp.where(ohp, badd[:, :, None], 0.0), axis=1)
            ohc = ((disp | realm)[:, :, None]
                   & ((G + pid_c)[:, :, None] == jnp.arange(C)[None, None, :]))
            tval = jnp.where(disp, tK + dispatch_ov[:, None], tK + total)
            hitc = jnp.any(ohc, axis=1)
            st["times"] = jnp.where(
                hitc, jnp.sum(jnp.where(ohc, tval[:, :, None], 0.0), axis=1),
                st["times"])
            st["seqs"] = jnp.where(
                hitc,
                jnp.sum(jnp.where(ohc, seq_at[:, :, None], i64(0)), axis=1),
                st["seqs"])
            hitp = jnp.any(ohp, axis=1)
            egv = jnp.where(disp, i64(-1), gj)
            st["end_g"] = jnp.where(
                hitp, jnp.sum(jnp.where(ohp, egv[:, :, None], i64(0)),
                              axis=1),
                st["end_g"])
            ohpr = (realm[:, :, None]
                    & (pid_c[:, :, None] == jnp.arange(P)[None, None, :]))
            st["end_rr"] = jnp.where(
                jnp.any(ohpr, axis=1),
                jnp.sum(jnp.where(ohpr, rrj[:, :, None], i64(0)), axis=1),
                st["end_rr"])
            st["del_n"] = jnp.where(mD, 0, st["del_n"])
            st["times"] = oh_set(st["times"], mD, C - 1, INF)
            st["seqs"] = oh_set(st["seqs"], mD, C - 1, BIGSEQ)

            st["it"] = st["it"] + 1
            return st

        times0 = jnp.full((W, C), INF)
        times0 = times0.at[:, :G].set(0.0)
        seqs0 = jnp.full((W, C), BIGSEQ, i64)
        seqs0 = seqs0.at[:, :G].set(jnp.arange(G, dtype=jnp.int64)[None, :])
        st0 = {
            "times": times0,
            "seqs": seqs0,
            "seq": jnp.full((W,), G, i64),
            "rel_seq": jnp.zeros((W,), i64),
            "src_rid": jnp.zeros((W, G), i64),
            "idle": jnp.broadcast_to(idle0, (W, P)),
            "end_g": jnp.full((W, P), -2, i64),
            "end_rr": jnp.full((W, P), -1, i64),
            "arrival": jnp.zeros((W, R)),
            "first_start": jnp.full((W, R), INF),
            "last_finish": jnp.zeros((W, R)),
            "done": jnp.zeros((W, R), i64),
            "pend": jnp.zeros((W, R, S), jnp.int32),
            "busy": jnp.zeros((W, P)),
            "tok": jnp.zeros((W,), i64),
            "del_pack": jnp.zeros((W, K), i64),
            "del_n": jnp.zeros((W,), i64),
            "zpos": jnp.zeros((W,), i64),
            "fpos": jnp.zeros((W,), i64),
            "events": jnp.zeros((W,), jnp.int32),
            "queued": jnp.zeros((W,), jnp.int32),
            "it": jnp.zeros((), i64),
        }
        out = lax.while_loop(cond, body, st0)
        return (out["arrival"], out["first_start"], out["last_finish"],
                out["done"], out["busy"], out["events"], out["queued"],
                out["it"])

    return advance


#: Diagnostics of the most recent :func:`run_batch_compiled` call:
#: ``{"iters", "itercap", "fallback"}``, plus ``"reason"`` on a
#: fallback. Tests and the simspeed benchmark read this to tell a compiled
#: run from a fallback.
last_stats: dict = {}

#: Running totals over every device run in this process: ``calls``, the
#: lock-step iterations they took (``iters``) and their budget
#: (``itercap``) — the lock-step loop pays for its longest lane. Lane
#: occupancy: ``lane_events`` sums, over real lanes, the iterations in
#: which the lane was active (one event each: an arrival, a deferred first
#: arrival, a completion or dispatch token, or a delivery drain), and
#: ``lane_slots`` is iterations × padded width, the lane-iterations the
#: device computed. ``queued`` sums, over real lanes, the task releases
#: that found their worker busy and waited in the ready queues.
#: The spans ``puzzle.batch.tables`` (host lane tables,
#: :func:`build_tables`) and ``puzzle.batch.lanes`` (lane assembly in the
#: analyzer's batch entry points) add ``<span>.ns`` / ``<span>.n`` here.
totals: Counter = Counter()

_advance_cache = None


def advance_fn() -> object:
    """The jitted lock-step loop ``advance(flags, tab)``, built once per
    process; ``flags`` is static, ``tab`` the arrays of :class:`LaneTables`."""
    global _advance_cache
    if _advance_cache is None:
        _advance_cache = _advance_factory()
    return _advance_cache


@dataclass
class LaneTables:
    """Host-side inputs of one compiled batch: ``advance(flags, tab)``'s
    arguments plus what :func:`run_batch_compiled` needs to unpack its
    outputs into a :class:`repro.core.batchsim.BatchResult`."""

    flags: Tuple
    tab: Dict[str, np.ndarray]
    lanes: List
    groups: List[List[int]]
    pids: List[int]
    nr: np.ndarray
    horizon: np.ndarray
    group_tasks: np.ndarray
    nr_bucket: int
    itercap: int


def build_tables(
    lanes: Sequence,
    groups: Sequence[Sequence[int]],
    processors: Sequence[Processor],
) -> Optional[LaneTables]:
    """Precompute a batch's lane tables on the host; ``None`` when the
    lanes' ready queues exceed :data:`QUEUE_PAIRS_MAX` pairs per request
    group, or their priorities the key's ``int32`` word.

    Arrival tables, noise multipliers and straggler multipliers use the
    scalar engines' exact expressions; every array is padded to its shape
    bucket so GA generations of jittering width share one compiled loop.
    """
    from .batchsim import BatchSimulator

    sim = BatchSimulator(lanes, groups, processors)
    lanes = sim.lanes
    groups = sim.groups
    pids = sim.pids
    (W, S, P, G, proc_of, prio_of, exec_v, quant_v, comm_v, total_v,
     dep_cnt, net_of, k_of, succ_pad, succ_cnt, dmax, roots, roots_n,
     jmax, group_tasks) = sim._pad_specs()

    nr = np.array([ln.num_requests for ln in lanes], np.int64)
    nr_max = int(nr.max())
    horizon = np.zeros(W)
    arrtab_raw = np.zeros((W, G, max(nr_max, 1)))
    for b, ln in enumerate(lanes):
        tables = draw_arrivals(ln.arrivals, ln.periods, ln.num_requests)
        for gi, tab in enumerate(tables):
            arrtab_raw[b, gi, :len(tab)] = tab
        horizon[b] = arrival_horizon(tables, ln.periods, ln.num_requests)

    dispatch_ov = np.array([ln.dispatch_overhead for ln in lanes])
    dispatch_pid = np.array([ln.dispatch_pid for ln in lanes], np.int64)
    dispatch_known = (dispatch_ov > 0) & np.isin(dispatch_pid, np.array(pids))
    dispatch_pid = np.clip(dispatch_pid, 0, P - 1)
    any_dispatch = bool(dispatch_known.any())
    overlap = np.array([ln.overlap_comm for ln in lanes], bool)

    # noise: z-draws + exp-multiplier tables, scalar-exact host-side
    noisy = np.zeros(W, bool)
    sigma_of = np.zeros((W, P))
    mu_of = np.zeros((W, P))
    draw_bound = np.zeros(W, np.int64)
    for b, ln in enumerate(lanes):
        if ln.noise is not None:
            noisy[b] = True
            for p in processors:
                s = ln.noise.sigma(p.kind)
                sigma_of[b, p.pid] = s
                mu_of[b, p.pid] = -0.5 * s * s
            draw_bound[b] = ln.num_requests * sum(
                ln.spec.counts[n] for nets in groups for n in nets)
    any_noise = bool(noisy.any())
    zcap = _bucket(int(draw_bound.max()) if any_noise else 1)
    emult = np.ones((W, zcap, P))
    for b in np.nonzero(noisy)[0]:
        rng = random.Random(lanes[b].noise.seed)
        bound = int(draw_bound[b])
        zs = [rng.gauss(0.0, 1.0) for _ in range(bound)]
        for p in pids:
            s = sigma_of[b, p]
            if s > 0.0:
                mu = mu_of[b, p]
                # the exact scalar expression: math.exp(mu + z * sigma)
                emult[b, :bound, p] = [math.exp(mu + z * s) for z in zs]

    # faults: straggler multipliers from the one-draw-per-delivery stream;
    # throttle/dropout windows as padded static tables
    faulted = np.zeros(W, bool)
    strag_on = np.zeros(W, bool)
    tmax = 1
    dmax_f = 1
    fb = np.zeros(W, np.int64)
    for b, ln in enumerate(lanes):
        if ln.faults is not None and not ln.faults.empty:
            faulted[b] = True
            tmax = max(tmax, len(ln.faults.throttles))
            dmax_f = max(dmax_f, len(ln.faults.dropouts))
            if ln.faults.straggler_prob > 0.0:
                strag_on[b] = True
                fb[b] = ln.num_requests * sum(
                    ln.spec.counts[n] for nets in groups for n in nets)
    any_fault = bool(faulted.any())
    any_strag = bool(strag_on.any())
    fcap = _bucket(int(fb.max()) if any_strag else 1)
    strag_tab = np.ones((W, fcap))
    thr_pid = np.full((W, tmax), -9, np.int64)
    thr_t0 = np.zeros((W, tmax))
    thr_t1 = np.zeros((W, tmax))
    thr_fac = np.ones((W, tmax))
    drop_pid = np.full((W, dmax_f), -9, np.int64)
    drop_t0 = np.zeros((W, dmax_f))
    drop_t1 = np.zeros((W, dmax_f))
    for b in np.nonzero(faulted)[0]:
        spec = lanes[b].faults
        for ti, (pid, t0, t1, fac) in enumerate(spec.throttles):
            thr_pid[b, ti] = pid
            thr_t0[b, ti], thr_t1[b, ti], thr_fac[b, ti] = t0, t1, fac
        for di, (pid, start, repair) in enumerate(spec.dropouts):
            drop_pid[b, di] = pid
            drop_t0[b, di] = start
            drop_t1[b, di] = (math.inf if repair is None
                              else start + repair)
        if strag_on[b]:
            rng = random.Random(spec.seed)
            prob = spec.straggler_prob
            inv_shape = 1.0 / spec.straggler_shape
            for k in range(int(fb[b])):
                u = rng.random()
                if u < prob:
                    # the exact scalar Pareto expression (FaultStream)
                    v = u / prob
                    if v >= 1.0:
                        v = math.nextafter(1.0, 0.0)
                    strag_tab[b, k] = (1.0 - v) ** (-inv_shape)
                else:
                    strag_tab[b, k] = 1.0

    idle0 = np.zeros(P, bool)
    idle0[pids] = True

    # ready queues: one key per (request, subgraph) pair of a request
    # group, priority << SEQ_BITS | release count, in an int32 word
    releases = 2 * int((nr * group_tasks.sum(axis=1)).max())  # and tokens
    if (nr_max * S > QUEUE_PAIRS_MAX or releases >= 1 << SEQ_BITS
            or int(prio_of.max()) >= 1 << (31 - SEQ_BITS)):
        return None

    # generous per-lane event bound: arrivals + completions (tasks +
    # dispatch tokens) + queue pops, doubled. Hitting it means a bug;
    # the caller falls back to numpy instead of hanging.
    task_max = int(group_tasks.sum(axis=1).max())
    itercap = 64 + 2 * (G * (nr_max + 2) + 4 * nr_max * task_max)

    # shape bucketing: pad W/S/NR (and the z/fault tables, bucketed above)
    # so GA generations with jittering widths reuse one compiled program.
    # Padding lanes carry horizon -1: their frontier (time 0) is never
    # active, so they are inert in every masked update. Width buckets to
    # multiples of 16 (not powers of two): per-iteration cost scales
    # ~linearly with W, so padding 80 GA lanes to 128 would cost ~60%.
    WB = max(16, -(-W // 16) * 16)
    SB = _bucket(S)
    NRB = _bucket(nr_max)
    jB = _bucket(jmax)
    dB = _bucket(dmax)

    def padw(a, fill=0):
        if a.shape[0] == WB:
            return a
        out = np.full((WB,) + a.shape[1:], fill, a.dtype)
        out[:W] = a
        return out

    def pad2(a, n, fill=0):
        if a.shape[1] == n:
            return a
        out = np.full((a.shape[0], n) + a.shape[2:], fill, a.dtype)
        out[:, :a.shape[1]] = a
        return out

    arrtab = np.zeros((W, G, NRB))
    arrtab[:, :, :arrtab_raw.shape[2]] = arrtab_raw
    succ_pad_b = np.zeros((W, SB, dB), np.int64)
    succ_pad_b[:, :S, :dmax] = succ_pad
    roots_b = np.zeros((W, G, jB), np.int64)
    roots_b[:, :, :jmax] = roots

    tab = {
        "arrtab": padw(arrtab),
        "horizon": padw(horizon, -1.0),
        "nr": padw(nr),
        "proc_of": padw(pad2(proc_of, SB)),
        "prio_of": padw(pad2(prio_of, SB)),
        "exec_v": padw(pad2(exec_v, SB)),
        "quant_v": padw(pad2(quant_v, SB)),
        "comm_v": padw(pad2(comm_v, SB)),
        "total_v": padw(pad2(total_v, SB)),
        "dep_cnt": padw(pad2(dep_cnt.astype(np.int32), SB)),
        "succ_pad": padw(succ_pad_b),
        "succ_cnt": padw(pad2(succ_cnt, SB)),
        "roots": padw(roots_b),
        "roots_n": padw(roots_n),
        "overlap": padw(overlap),
        "dispatch_ov": padw(dispatch_ov),
        "dispatch_pid": padw(dispatch_pid),
        "dispatch_known": padw(dispatch_known),
        "noisy": padw(noisy),
        "sigma_pos": padw(sigma_of > 0.0),
        "emult": padw(emult, 1.0),
        "faulted": padw(faulted),
        "strag_on": padw(strag_on),
        "strag_tab": padw(strag_tab, 1.0),
        "thr_pid": padw(thr_pid, -9),
        "thr_t0": padw(thr_t0),
        "thr_t1": padw(thr_t1),
        "thr_fac": padw(thr_fac, 1.0),
        "drop_pid": padw(drop_pid, -9),
        "drop_t0": padw(drop_t0),
        "drop_t1": padw(drop_t1),
        "idle0": idle0,
        "itercap": np.int64(itercap),
    }
    flags = (G, P, any_noise, any_fault, any_strag, any_dispatch)
    return LaneTables(
        flags=flags, tab=tab, lanes=lanes, groups=groups, pids=pids, nr=nr,
        horizon=horizon, group_tasks=group_tasks, nr_bucket=NRB,
        itercap=itercap)


def run_batch_compiled(
    lanes: Sequence,
    groups: Sequence[Sequence[int]],
    processors: Sequence[Processor],
) -> Optional[object]:
    """Run a batch through the compiled core; ``None`` requests fallback.

    Builds the lane tables on the host (:func:`build_tables`), then the
    jitted loop advances the shared frontier to quiescence. Returns a
    :class:`repro.core.batchsim.BatchResult` (``tasks=None``), or ``None``
    with ``last_stats["reason"]`` set when the ready queues exceed their
    bound (:func:`build_tables`) or the iteration guard tripped — the
    caller reruns on the bit-exact numpy tier then.
    """
    import jax

    from .batchsim import BatchResult

    last_stats.clear()
    with span("puzzle.batch.tables", totals):
        t = build_tables(lanes, groups, processors)
    if t is None:
        last_stats.update(fallback=True, iters=0, itercap=0,
                          reason="queue-bound")
        return None
    W = len(t.lanes)
    with jax.enable_x64(True):
        jtab = {k: jax.numpy.asarray(v) for k, v in t.tab.items()}
        (arrival, first_start, last_finish, done, busy, events, queued,
         iters) = advance_fn()(t.flags, jtab)
        iters = int(iters)
        fallback = iters >= t.itercap
        last_stats.update(iters=iters, itercap=t.itercap, fallback=fallback)
        totals.update(calls=1, iters=iters, itercap=t.itercap,
                      lane_events=int(np.asarray(events)[:W].sum()),
                      queued=int(np.asarray(queued)[:W].sum()),
                      lane_slots=iters * len(t.tab["horizon"]))
        if fallback:
            last_stats["reason"] = "itercap"
            return None
        arrival = np.asarray(arrival)[:W]
        first_start = np.asarray(first_start)[:W]
        last_finish = np.asarray(last_finish)[:W]
        done = np.asarray(done)[:W]
        busy = np.asarray(busy)[:W]

    return BatchResult(
        lanes=t.lanes, groups=t.groups, num_requests=t.nr, arrival=arrival,
        first_start=first_start, last_finish=last_finish, done=done,
        group_tasks=t.group_tasks, busy=busy, horizon=t.horizon,
        pids=t.pids, nr_max=t.nr_bucket, tasks=None,
    )
