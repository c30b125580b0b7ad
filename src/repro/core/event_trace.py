"""Whole-run event-trace compiler — the AFL event loop as ONE device program.

PRs 1-3 fused the blends (``agg_engine``), the local SGD
(``client_plane``) and sharded the fleet, but the AFL *event loop* itself
stayed host-driven: ``run_afl`` walks the scheduler generator one window
at a time, paying a host→device round trip per window (and per-event jit
dispatch for the blends).  On dispatch-bound accelerator hosts that hop
is the dominant cost of the simulation — and it is entirely avoidable,
because the scheduler is a *pure function* of (fleet, tau_u, tau_d): no
randomness, no feedback from the learning state.  docs/DESIGN.md §7.

This module therefore splits the run into a host-side COMPILE step and a
device-side EXECUTE step:

* ``compile_afl_trace`` runs the scheduler ONCE on the host and lowers
  the full timeline into dense per-event arrays — uploader cid,
  staleness, the §III coefficient β_j (the staleness tracker is a cheap
  scalar recurrence, replayed exactly), retrain step counts, retrain
  seeds, window/broadcast boundaries.  The trace is plain NumPy: pure
  control plane, no device state.
* ``group_segments`` buckets the per-event scan lengths (pow2, shared
  policy with ``agg_engine.pow2_bucket``) and groups the trace into
  contiguous same-bucket segments, merging runs shorter than ``min_run``
  upward into their larger-bucket neighbor.  Heavily interleaved bucket
  sequences collapse toward ONE max-bucket segment; long homogeneous
  phases keep their own tighter program.  Event order is never permuted.
* ``CompiledLoopRunner`` executes each segment as ONE jitted,
  buffer-donated ``lax.scan`` over the trace slice: every scan step
  ``dynamic_slice``s the uploader's row, applies the eq. (3) blend (or
  the FedOpt pseudo-gradient + server optimizer) to the carried global
  flat buffer, retrains the row with the client plane's scanned local
  SGD, and scatters it back — carrying ``(fleet_buf, g_flat, opt_state)``
  with ``donate_argnums=(0, 1)`` so on TPU/GPU no buffer copy survives
  between events.  A whole ≥300-event run is O(#buckets) launches
  instead of O(#windows) (asserted by tests via the runner's
  launch/trace instrumentation, not timing).

The sharded fleet plane rides the same trace: the segment program is
wrapped in ``jax.shard_map`` over the plane's ``fleet`` mesh — the
owning shard contributes the uploader's row through a one-row psum and
masks the row write-back, exactly like the per-event
``ShardedRowEngine`` blends, so the compiled run matches the
single-device plane ≤1e-5 at M=64 (tests/test_event_trace.py).

``run_afl(..., compiled_loop=True)`` / ``launch/train.py --loop
compiled`` are the entry points; eval points and the baseline's every-M
broadcast split the run into chunks (one extra launch per boundary).

The sweep plane (``core/sweep_plane.py``, DESIGN.md §8) builds on the
same machinery: ``compile_afl_trace(events=...)`` replays per-run
coefficients over a SHARED scheduler simulation (runs that pin the
device population have identical timelines), ``make_scan_step`` /
``make_segment_fn`` grow ``run_batched=True`` twins that carry a
leading run axis (donated whole), and ``stack_segment_inputs`` fills
the (L, R, ...) scan inputs for R structure-matched traces in one pass.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core import guards as _guards
from repro.core.agg_engine import pow2_bucket
from repro.core.scheduler import (AFLScheduler, BaselineAFLScheduler,
                                  ClientSpec, UploadEvent)


class RunInterrupted(RuntimeError):
    """Raised by the compiled-loop runner / windowed loop when a
    ``stop_flag`` fires mid-run: the run state has already been flushed
    through the autosave hook, so the caller can exit (or re-enter with
    ``--resume``) without losing progress.  ``cursor`` is the number of
    events durably consumed."""

    def __init__(self, cursor: int):
        super().__init__(
            f"run interrupted at event {cursor} (state saved)")
        self.cursor = int(cursor)


# ---------------------------------------------------------------------------
# Host-side trace compilation (pure control plane)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EventTrace:
    """Dense device-ready view of one whole AFL run's timeline.

    All arrays have length E = number of upload events.  ``betas`` holds
    the per-event β_j EXACTLY as ``run_afl`` would compute it (staleness
    tracker replayed, ``max_staleness`` drops already applied as β=1);
    ``seeds`` is the per-event retrain seed (the broadcast retrain of the
    baseline algorithm uses the same ``seed·100003 + j`` formula, so
    ``seeds[i]`` serves both).  ``s_buckets`` (pow2 bucket id of each
    event's staged batch count) is filled in by the runner's staging pass
    — it depends on the task's ``batch_fn``, not on the schedule.
    """
    events: List[UploadEvent]
    cids: np.ndarray            # (E,) int32  uploader per event
    js: np.ndarray              # (E,) int32  global iteration (1-based)
    staleness: np.ndarray       # (E,) int32
    betas: np.ndarray           # (E,) float64  β_j per event
    local_steps: np.ndarray     # (E,) int32  retrain K per event
    seeds: np.ndarray           # (E,) int64  retrain seed per event
    t_complete: np.ndarray      # (E,) float64  virtual aggregation time
    broadcast: np.ndarray       # (E,) bool  baseline every-M broadcast AFTER
    algorithm: str
    M: int
    base_seed: int
    s_buckets: Optional[np.ndarray] = None   # (E,) int32, runner-filled
    # fault-injection plane (core/faults.py, DESIGN.md §9) — compile
    # always fills these; fault-dropped events keep their slot (β=1
    # identity coefficients) and execute as masked no-op steps
    dropped: Optional[np.ndarray] = None     # (E,) bool  fault-dropped
    stale_drop: Optional[np.ndarray] = None  # (E,) bool  max_staleness drop
    attempts: Optional[np.ndarray] = None    # (E,) int32 upload attempts
    outcomes: Optional[np.ndarray] = None    # (E,) int8  OUTCOME_* codes
    base_events: Optional[List[UploadEvent]] = None  # clean timeline

    def __len__(self) -> int:
        return len(self.cids)

    @property
    def per_event_retrain(self) -> bool:
        """eq. (4): only the uploader retrains — except the §III-B
        baseline, where clients keep the cycle-start model and the fleet
        retrains wholesale at the every-M broadcast."""
        return self.algorithm != "afl_baseline"


def compile_afl_trace(fleet: Sequence[ClientSpec], *, algorithm: str,
                      iterations: int, tau_u: float, tau_d: float,
                      gamma: float = 0.4, mu_momentum: float = 0.9,
                      max_staleness: Optional[int] = None,
                      seed: int = 0,
                      events: Optional[List[UploadEvent]] = None,
                      faults=None, realized: bool = False) -> EventTrace:
    """Run the scheduler once on the host and precompute every scalar the
    event loop would: the timeline, the §III coefficients, the retrain
    seeds.  Mirrors ``run_afl``'s coefficient logic exactly (same float
    ops in the same order — the β replay is vectorized numpy over the
    event arrays, so million-event traces stay cheap to stage), so trace
    replay is bit-consistent with the Python loop up to data-plane
    rounding.

    ``events`` short-circuits the scheduler simulation with a
    precomputed timeline: the event stream is a pure function of the
    fleet's (τ_m, K_m) and (tau_u, tau_d), so runs that share the device
    population (the sweep plane's ``Scenario.fleet_seed`` pinning,
    DESIGN.md §8) share ONE host simulation while the per-run §III
    coefficients (α from this run's partition sizes, staleness replay)
    and retrain seeds are still computed per call.  ``events`` must be
    the CLEAN timeline (``EventTrace.base_events``) — ``faults`` (a
    ``FaultModel`` / preset name / kwargs dict, ``core/faults.py``) is
    realized HERE, per call, so shared-timeline sweep runs don't
    double-apply it.  Fault-dropped events keep their slot with β=1 and
    ``dropped=True`` (masked no-op steps); deferred/retried events carry
    their REALIZED staleness into the eq. (11) replay, whose tracker
    skips fault-dropped uploads (the server never saw them)."""
    from repro.core import faults as flt

    if realized and events is None:
        raise ValueError("realized=True replays a recorded timeline — "
                         "pass events")
    if realized and faults is not None:
        raise ValueError("realized events already carry their fault "
                         "outcomes; faults= would double-apply them")

    M = len(fleet)
    alpha = agg.sfl_alpha([c.num_samples for c in fleet])
    if algorithm == "afl_baseline":
        sched = BaselineAFLScheduler(fleet, tau_u=tau_u, tau_d=tau_d)
        cycle_betas = agg.solve_betas(alpha, sched.cycle_order())
    elif algorithm in ("afl_alpha", "csmaafl"):
        sched = AFLScheduler(fleet, tau_u=tau_u, tau_d=tau_d)
    else:
        raise ValueError(f"unknown AFL algorithm '{algorithm}'")
    if events is None:
        events = sched.trace(iterations)
    elif len(events) != iterations:
        raise ValueError(f"precomputed timeline has {len(events)} events, "
                         f"expected {iterations}")
    base_events = events
    E = len(events)
    fm = flt.resolve_faults(faults)
    if realized:
        # the recorded stream (an ingest session's arrival log) already
        # went through the fault plane LIVE: each UploadEvent carries its
        # outcome / attempts / realized staleness, so replay just reads
        # them back instead of re-rolling the transform
        base_events = None
        dropped = np.asarray([ev.outcome != flt.OUTCOME_OK
                              for ev in events], bool)
        attempts = np.asarray([ev.attempts for ev in events], np.int32)
        outcomes = np.asarray([ev.outcome for ev in events], np.int8)
    elif fm is not None and fm.active():
        real = flt.realize_events(base_events, fm, algorithm=algorithm,
                                  M=M, tau_u=tau_u, seed=seed)
        events = real.events
        dropped, attempts, outcomes = real.dropped, real.attempts, \
            real.outcomes
    else:
        dropped = np.zeros(E, bool)
        attempts = np.ones(E, np.int32)
        outcomes = np.zeros(E, np.int8)
    js = np.fromiter((ev.j for ev in events), np.int64, E)
    cids = np.fromiter((ev.cid for ev in events), np.int64, E)
    iis = np.fromiter((ev.i for ev in events), np.int64, E)
    stal = np.fromiter((ev.staleness for ev in events), np.int64, E)
    # vectorized β replay (same float ops in the same order as the
    # scalar loop in run_afl, elementwise)
    omb = np.zeros(E, np.float64)
    act = ~dropped
    if algorithm == "afl_alpha":
        omb[act] = alpha[cids[act]]
    elif algorithm == "afl_baseline":
        omb[act] = 1.0 - cycle_betas[(js[act] - 1) % M]
    else:   # csmaafl, eq. (11) — tracker updated on every ACCEPTED
        # event (incl. max_staleness drops, matching the Python loop);
        # fault-dropped uploads never reach the server
        s_act = np.maximum(stal[act].astype(np.float64), 1.0)
        mu = agg.ema_sequence(s_act, mu_momentum)
        ja = js[act].astype(np.float64)
        ga = np.maximum(js[act] - iis[act], 1).astype(np.float64)
        omb[act] = np.minimum(1.0, mu / (gamma * ja * ga))
    stale_drop = np.zeros(E, bool)
    if max_staleness is not None:
        stale_drop = act & (stal > max_staleness)
        omb[stale_drop] = 0.0
    if algorithm == "afl_baseline":
        bcast = js % M == 0
    else:
        bcast = np.zeros(E, bool)
    return EventTrace(
        events=events,
        cids=cids.astype(np.int32),
        js=js.astype(np.int32),
        staleness=stal.astype(np.int32),
        betas=1.0 - omb,
        local_steps=np.asarray([ev.local_steps for ev in events], np.int32),
        seeds=seed * 100003 + js,
        t_complete=np.asarray([ev.t_complete for ev in events], np.float64),
        broadcast=bcast,
        algorithm=algorithm, M=M, base_seed=seed,
        dropped=dropped, stale_drop=stale_drop, attempts=attempts,
        outcomes=outcomes, base_events=base_events)


# ---------------------------------------------------------------------------
# Bucket grouping (order-preserving)
# ---------------------------------------------------------------------------
def group_segments(buckets: Sequence[int], *, min_run: int = 16
                   ) -> List[Tuple[int, int, int]]:
    """Group per-event scan-length buckets into contiguous
    ``(start, stop, bucket)`` segments.

    Maximal equal-bucket runs shorter than ``min_run`` are merged into
    the neighboring run with the LARGER bucket (shorter events pad up
    under their valid-masks — merges never truncate), then adjacent
    equal-bucket runs coalesce.  This bounds the launch count: a heavily
    interleaved bucket sequence collapses toward one max-bucket segment,
    while long homogeneous phases keep their own tighter program.  The
    segments concatenate to ``[0, len(buckets))`` in order — event order
    is never permuted.
    """
    buckets = [int(b) for b in buckets]
    if not buckets:
        return []
    runs: List[List[int]] = []
    s = 0
    for i in range(1, len(buckets) + 1):
        if i == len(buckets) or buckets[i] != buckets[s]:
            runs.append([s, i, buckets[s]])
            s = i
    changed = True
    while changed and len(runs) > 1:
        changed = False
        for idx, run in enumerate(runs):
            if run[1] - run[0] >= min_run:
                continue
            nbrs = [j for j in (idx - 1, idx + 1) if 0 <= j < len(runs)]
            j = max(nbrs, key=lambda k: runs[k][2])
            lo, hi = sorted((idx, j))
            runs[lo] = [runs[lo][0], runs[hi][1],
                        max(runs[lo][2], runs[hi][2])]
            del runs[hi]
            changed = True
            break
    out = [runs[0]]
    for r in runs[1:]:
        if r[2] == out[-1][2]:
            out[-1] = [out[-1][0], r[1], r[2]]
        else:
            out.append(r)
    return [(r[0], r[1], r[2]) for r in out]


# ---------------------------------------------------------------------------
# Shared segment builders (single-run and run-batched)
# ---------------------------------------------------------------------------
def _evmask(ev, a, o):
    """``jnp.where(ev, a, o)`` with ``ev`` broadcast along ``a``'s
    TRAILING axes — ``ev`` is a scalar in the single-run form and a
    per-run ``(R,)`` vector in the run-batched form (faults give each
    run its own drop pattern inside one structure-matched group)."""
    e = jnp.reshape(ev, jnp.shape(ev) + (1,) * (jnp.ndim(a) - jnp.ndim(ev)))
    return jnp.where(e, a, o)


def make_scan_step(base_engine, scan_train, s_update, server_lr: float,
                   retrain: bool, *, run_batched: bool = False,
                   guards: Optional[_guards.GuardConfig] = None):
    """The per-event body shared by the compiled loop and the sweep
    plane: (optionally) guard the uploader's (already gathered) row(s),
    blend the carried global(s) against them, optionally retrain.
    Returns ``step(g, opt, gs, row, cf, ev, b, sv, lstep) ->
    (g_new, opt_new, gs_new, row_new|None, ev_eff)`` — ``lstep`` is the
    plane's local step (``ClientPlane._lstep``), handed to
    ``scan_train`` — ``ev_eff`` is the
    write-back / state-advance mask (``ev & guard_ok``; just ``ev`` when
    guards are off, and ``gs`` passes through untouched).

    A guard rejection is the PR 6 drop mechanism applied device-side:
    the global model and optimizer state come back through
    ``where``-masks keyed on ``ev_eff`` (identity step), and the caller
    masks the retrain write-back with ``ev_eff`` so the rejected row
    never lands in the fleet either.

    With ``run_batched=True`` every array carries a leading run axis R —
    the blend goes through the engine's run-batched expressions
    (``blend_runs_expr`` / ``delta_runs_expr``), the retrain vmaps the
    plane's scanned local SGD across runs, the server optimizer vmaps
    its update across runs (each run owns its state slice, so per-run
    fault drops freeze only that run's state), the guard vmaps its
    decision (each run owns its median tracker and counters), and ``ev``
    is the per-run ``(R,)`` validity vector (pad slots are invalid in
    every run; fault-dropped slots only in their own run)."""
    if run_batched:
        blend = base_engine.blend_runs_expr
        delta = base_engine.delta_runs_expr
        train = jax.vmap(scan_train, in_axes=(0, 0, 0, None))
        s_upd = (None if s_update is None
                 else jax.vmap(s_update, in_axes=(0, 0, 0, None)))
    else:
        blend = base_engine.blend_row_expr
        delta = base_engine.delta_row_expr
        train = scan_train
        s_upd = s_update
    lr = server_lr
    gupd = None
    if guards is not None:
        gupd = functools.partial(_guards.guard_update, guards)
        if run_batched:
            gupd = jax.vmap(gupd)

    def step(g, opt, gs, row, cf, ev, b, sv, lstep):
        if gupd is None:
            eve, row_eff = ev, row
        else:
            ok, row_eff, gs = gupd(g, row, gs, ev)
            eve = ev & ok
        if s_upd is None:
            # dropped/padded slots carry identity coefficients (β=1) —
            # the blend is an exact no-op, no masking needed; guard
            # rejections DO need the mask (a NaN row poisons the blend
            # output even under identity-adjacent coefficients)
            g2 = blend(g, row_eff, cf)
            if gupd is not None:
                g2 = _evmask(eve, g2, g)
        else:
            pg = delta(g, row_eff, cf[..., 1])
            g2, opt2 = s_upd(g, pg, opt, lr)
            # dropped/padded/rejected slots must not advance the global
            # model or the optimizer state
            g2 = _evmask(eve, g2, g)
            opt = jax.tree.map(
                functools.partial(_evmask, eve), opt2, opt)
        new = train(g2, b, sv, lstep) if retrain else None
        return g2, opt, gs, new, eve

    return step


def make_segment_fn(step_fn, *, run_batched: bool = False):
    """One scan segment over a trace slice as a traceable function of
    ``(fleet_buf, g_flat, opt_state, gstate, cids, coefs, evalid,
    batches, svalid, lstep)`` (``lstep``: the plane's local step).  ``gstate`` is the guard carry (``()`` when
    guards are off — it rides the scan carry either way so the segment
    signature is uniform).  The single-run form carries
    ``((M, n), (n,), opt, gs)`` and per-event xs with leading axis L;
    the run-batched form carries ``((R, M, n), (R, n), opt, gs)`` with
    xs of shape (L, R, ...) — the SAME event order executes for R runs
    at once, and ``donate_argnums=(0, 1)`` on the jitted wrapper donates
    the whole stacked run axis."""
    if not run_batched:

        def seg(fleet_buf, g_flat, opt_state, gstate, cids, coefs,
                evalid, batches, svalid, lstep):
            def step(carry, xs):
                buf, g, opt, gs = carry
                cid, cf, ev, b, sv = xs
                row = jax.lax.dynamic_slice_in_dim(buf, cid, 1, axis=0)[0]
                g2, opt, gs, new, eve = step_fn(
                    g, opt, gs, row, cf, ev, b, sv, lstep)
                if new is not None:
                    new = jnp.where(eve, new.astype(buf.dtype), row)
                    buf = jax.lax.dynamic_update_slice_in_dim(
                        buf, new[None], cid, axis=0)
                return (buf, g2, opt, gs), None
            (buf, g, opt, gs), _ = jax.lax.scan(
                step, (fleet_buf, g_flat, opt_state, gstate),
                (cids, coefs, evalid, batches, svalid))
            return buf, g, opt, gs

        return seg

    gather = jax.vmap(
        lambda bu, c: jax.lax.dynamic_slice_in_dim(bu, c, 1, axis=0)[0])
    scatter = jax.vmap(
        lambda bu, nr, c: jax.lax.dynamic_update_slice_in_dim(
            bu, nr[None], c, axis=0))

    def seg_runs(fleet_bufs, g_flats, opt_state, gstate, cids, coefs,
                 evalid, batches, svalid, lstep):
        def step(carry, xs):
            bufs, g, opt, gs = carry
            cid, cf, ev, b, sv = xs
            rows = gather(bufs, cid)
            g2, opt, gs, new, eve = step_fn(g, opt, gs, rows, cf, ev, b, sv,
                                            lstep)
            if new is not None:
                # eve is (R,): a fault-dropped or guard-rejected slot
                # keeps that run's row
                new = _evmask(eve, new.astype(bufs.dtype), rows)
                bufs = scatter(bufs, new, cid)
            return (bufs, g2, opt, gs), None
        (bufs, g, opt, gs), _ = jax.lax.scan(
            step, (fleet_bufs, g_flats, opt_state, gstate),
            (cids, coefs, evalid, batches, svalid))
        return bufs, g, opt, gs

    return seg_runs


def segment_inputs(trace: EventTrace, staged, s0: int, s1: int,
                   s_bucket: int, *, fedopt: bool):
    """Dense padded scan inputs for ``trace[s0:s1]`` — the host-side half
    of one segment launch, shared by the single-run runner and the sweep
    plane (which stacks R runs' outputs on a new axis).  Returns numpy
    ``(cids, coefs, evalid, batches, svalid)`` with leading axis
    ``Lb = pow2_bucket(s1 - s0)``; pad slots carry identity coefficients
    and ``evalid=False``."""
    from repro.core.client_plane import _pad_batches

    L = s1 - s0
    Lb = pow2_bucket(L)
    pad = Lb - L
    if trace.per_event_retrain:
        trees, svalid = [], []
        for i in range(s0, s1):
            b, nb = staged[i]
            trees.append(_pad_batches(b, s_bucket))
            svalid.append(np.arange(s_bucket) < nb)
        trees += trees[:1] * pad
        batches = jax.tree.map(lambda *xs: np.stack(xs), *trees)
        svalid = np.stack(svalid + [np.zeros(s_bucket, bool)] * pad)
    else:
        # §III-B baseline: blends only; a zero-width step placeholder
        # keeps the scan xs structure uniform
        batches = np.zeros((Lb, 0), np.float32)
        svalid = np.zeros((Lb, 0), bool)
    cids = np.concatenate(
        [trace.cids[s0:s1], np.zeros(pad, np.int32)])
    betas = trace.betas[s0:s1]
    cf0 = betas.astype(np.float32)
    if not fedopt:
        # mirrors run_afl: coefs = [f32(β), f32(1) − f32(β)]
        cf1 = np.float32(1.0) - cf0
    else:
        # mirrors run_afl's delta path: scale = f32(1 − β)
        cf1 = (1.0 - betas).astype(np.float32)
    coefs = np.stack([cf0, cf1], axis=1)
    coefs = np.concatenate(
        [coefs, np.tile(np.asarray([[1.0, 0.0]], np.float32),
                        (pad, 1))]).astype(np.float32)
    # fault-dropped events execute as masked no-op steps: identity
    # coefs (β=1 from the replay) + evalid=False blocks the retrain
    # write-back and the FedOpt state advance
    live = (np.ones(L, bool) if trace.dropped is None
            else ~trace.dropped[s0:s1])
    evalid = np.concatenate([live, np.zeros(pad, bool)])
    return cids, coefs, evalid, batches, svalid


def stack_segment_inputs(traces: Sequence[EventTrace], stageds,
                         s0: int, s1: int, s_bucket: int, *,
                         fedopt: bool):
    """Run-stacked scan inputs for R structure-matched traces: the
    (L, R, ...) twin of :func:`segment_inputs`, filled directly into
    preallocated arrays (one copy per event per run — no per-run
    intermediate stacks, which would double the sweep's host time).
    Pad events (beyond L up to the pow2 launch width) carry zero batches
    with ``evalid=False`` — identity blends, masked-out retrains."""
    R = len(traces)
    L = s1 - s0
    Lb = pow2_bucket(L)
    retrain = traces[0].per_event_retrain
    cids = np.zeros((Lb, R), np.int32)
    coefs = np.empty((Lb, R, 2), np.float32)
    coefs[L:] = (1.0, 0.0)
    # evalid is PER RUN (Lb, R): pads are invalid everywhere, fault
    # drops only in their own run (each run has its own realization)
    evalid = np.zeros((Lb, R), bool)
    for k, trace in enumerate(traces):
        evalid[:L, k] = (True if trace.dropped is None
                         else ~trace.dropped[s0:s1])
        cids[:L, k] = trace.cids[s0:s1]
        betas = trace.betas[s0:s1]
        cf0 = betas.astype(np.float32)
        coefs[:L, k, 0] = cf0
        if not fedopt:
            # mirrors run_afl: coefs = [f32(β), f32(1) − f32(β)]
            coefs[:L, k, 1] = np.float32(1.0) - cf0
        else:
            # mirrors run_afl's delta path: scale = f32(1 − β)
            coefs[:L, k, 1] = (1.0 - betas).astype(np.float32)
    if not retrain:
        return (cids, coefs, evalid, np.zeros((Lb, R, 0), np.float32),
                np.zeros((Lb, R, 0), bool))
    first = stageds[0][s0][0]
    if isinstance(first, np.ndarray) and first.shape[0] == s_bucket:
        # uniform single-array staging (the dispatch-light common case:
        # every event stages exactly s_bucket steps of one ndarray leaf):
        # ONE C-level stack straight into the (Lb, R, ...) layout
        # instead of L x R Python-side assignments
        rows, uniform = [], True
        for i in range(s0, s1):            # event-major == axis-0 order
            for staged in stageds:
                b, nb = staged[i]
                if not (isinstance(b, np.ndarray)
                        and nb == s_bucket == b.shape[0]):
                    uniform = False
                    break
                rows.append(b)
            if not uniform:
                break
        if uniform:
            batches = np.zeros((Lb, R) + first.shape, first.dtype)
            np.stack(rows, out=batches[:L].reshape((L * R,) + first.shape))
            svalid = np.zeros((Lb, R, s_bucket), bool)
            svalid[:L] = True
            return cids, coefs, evalid, batches, svalid
    leaves0, treedef = jax.tree.flatten(stageds[0][s0][0])
    batch_arrs = [np.zeros((Lb, R, s_bucket) + np.shape(x)[1:],
                           np.asarray(x).dtype) for x in leaves0]
    svalid = np.zeros((Lb, R, s_bucket), bool)
    for k, staged in enumerate(stageds):
        for i in range(s0, s1):
            b, nb = staged[i]
            for arr, x in zip(batch_arrs, treedef.flatten_up_to(b)):
                arr[i - s0, k, :nb] = x
            svalid[i - s0, k, :nb] = True
    batches = jax.tree.unflatten(treedef, batch_arrs)
    return cids, coefs, evalid, batches, svalid


def stage_trace_events(plane, trace: EventTrace, start: int = 0):
    """Stage every event's batches once (host NumPy) and annotate the
    trace with each event's pow2 scan-length bucket id.  Returns the
    per-event ``(batches, num_batches)`` list (entries before ``start``
    are None).  Shared by the compiled-loop runner and the sweep plane."""
    staged: List[Optional[Tuple[Any, int]]] = [None] * start
    buckets = np.zeros(len(trace), np.int32)
    stage = plane._staged_batches
    bucketed = plane._bucketed
    cids, steps, seeds = trace.cids, trace.local_steps, trace.seeds
    for i in range(start, len(trace)):
        b = stage(int(cids[i]), int(steps[i]), int(seeds[i]))
        # ndarray fast path: tree_leaves costs ~2us per event, which is
        # real money at sweep scale (R x E events staged per pass)
        nb = (b.shape[0] if isinstance(b, np.ndarray)
              else int(jax.tree.leaves(b)[0].shape[0]))
        staged.append((b, int(nb)))
        buckets[i] = bucketed(nb)
    trace.s_buckets = buckets
    return staged


def split_for_slots(cid_cols, s0: int, s1: int, cap: int
                    ) -> List[Tuple[int, int]]:
    """Split ``[s0, s1)`` into sub-ranges each naming ≤ ``cap`` unique
    cids per run column — the paged plane's launch-width constraint (the
    slot pool holds at most P rows, so one scan segment may address at
    most P distinct clients).  Greedy left-to-right: cut immediately
    before the event that would push any column past the cap, which is
    deterministic and replayable (the prefetch plan and the executor
    derive the identical sub-ranges).  ``cid_cols`` is the full-trace
    cid array, (E,) single-run or (E, R) run-stacked."""
    cols = np.asarray(cid_cols)
    if cols.ndim == 1:
        cols = cols[:, None]
    R = cols.shape[1]
    out: List[Tuple[int, int]] = []
    t0 = s0
    seen: List[set] = [set() for _ in range(R)]
    for i in range(s0, s1):
        grown = [seen[k] | {int(cols[i, k])} for k in range(R)]
        if i > t0 and max(len(s) for s in grown) > cap:
            out.append((t0, i))
            t0 = i
            seen = [{int(cols[i, k])} for k in range(R)]
        else:
            seen = grown
    if s1 > t0:
        out.append((t0, s1))
    return out


def boundary_cuts(trace: EventTrace, *, start: int = 0,
                  eval_every: Optional[int] = None) -> List[int]:
    """Chunk boundaries of ``trace[start:]``: eval points (``js`` divisible
    by ``eval_every``; None = no eval cuts) and the §III-B every-M
    broadcasts, plus the trace end.  Shared by the compiled-loop runner
    and the sweep plane — two runs with the same (algorithm, iterations,
    eval cadence) cut at identical positions, which is what lets their
    segments stack on a run axis."""
    cuts = {len(trace)}
    for i in range(start, len(trace)):
        if trace.broadcast[i]:
            cuts.add(i + 1)
        if eval_every is not None and trace.js[i] % eval_every == 0:
            cuts.add(i + 1)
    return sorted(cuts)


# ---------------------------------------------------------------------------
# Device-side execution: segments as donated lax.scan programs
# ---------------------------------------------------------------------------
class CompiledLoopRunner:
    """Execute a compiled :class:`EventTrace` against a client plane.

    One instance owns the jitted segment programs (cached per batch-tree
    structure; per-shape retraces are counted by ``variants()``) and the
    launch instrumentation the tests assert on:

    * ``launches``  — number of jitted program invocations performed
      (segments + the fleet-init / broadcast ``train_all`` calls);
    * ``segments``  — number of scan segments executed;
    * ``variants()``— total TRACED program variants across the cached
      jitted functions (the honest "no recompile-per-event" signal).

    ``min_run`` is the :func:`group_segments` merge threshold.  The
    runner works for both the single-device :class:`ClientPlane` and the
    :class:`ShardedClientPlane` (detected by its ``mesh``): the sharded
    segment program wraps the same scan in ``jax.shard_map``, resolves
    cid → (shard, local row) in-program and psum-gathers only the
    addressed row, mirroring ``ShardedRowEngine``.
    """

    def __init__(self, plane, *, server_opt: Optional[str] = None,
                 server_lr: float = 1.0, min_run: int = 16, guards=None):
        self.plane = plane
        self.engine = plane.engine
        # the base AggEngine (the sharded plane wraps it) fixes the blend
        # math + storage dtype; its traceable row exprs inline into scan
        self.base_engine = getattr(plane.engine, "base", plane.engine)
        self.server_opt = server_opt
        self.server_lr = server_lr
        self.min_run = min_run
        self.sharded = getattr(plane, "mesh", None) is not None
        self.paged = getattr(plane, "paged", False)
        self.guards = _guards.resolve_guards(guards)
        self._s_update = None
        if server_opt is not None:
            from repro.optim import optimizers as _opt
            _, self._s_update = _opt.get_optimizer(server_opt)
        # compiled segment programs live ON THE PLANE (shared by every
        # runner over it, like the plane's own train programs), so a
        # second compiled run reuses the compiled scan instead of paying
        # trace+compile again; keys carry (server_opt, server_lr, guard
        # cfg) since the optimizer update / guard expression are closed
        # over
        self._progs: Dict[Any, Any] = plane.__dict__.setdefault(
            "_compiled_progs", {})
        self._prog_ctx = (server_opt, float(server_lr),
                          None if self.guards is None else self.guards.key())
        self.launches = 0
        self.segments = 0

    # -- instrumentation -----------------------------------------------------
    def variants(self) -> int:
        total = 0
        for prog in self._progs.values():
            size = getattr(prog, "_cache_size", None)
            total += int(size()) if callable(size) else 1
        return total

    def count_launch(self, n: int = 1) -> None:
        """Record jitted launches performed on the runner's behalf by
        the plane (fleet init, baseline broadcasts)."""
        self.launches += n

    # -- program builders ----------------------------------------------------
    def _scan_step(self, retrain: bool):
        return make_scan_step(self.base_engine, self.plane._scan_train,
                              self._s_update, self.server_lr, retrain,
                              guards=self.guards)

    def _build_prog(self, retrain: bool):
        seg = make_segment_fn(self._scan_step(retrain))
        dn = (0, 1) if self.plane.donate else ()
        return jax.jit(seg, donate_argnums=dn)

    def _build_sharded_prog(self, retrain: bool, batches_proto, opt_proto):
        from jax.sharding import PartitionSpec as P

        from repro.sharding.specs import FLEET_AXIS, fleet_buffer_spec

        plane = self.plane
        base = self.base_engine
        storage = base.storage_dtype
        use_kernel = base.mode == "kernel"
        kern = base.kern
        m_loc = plane.layout.rows_per_shard
        ax = FLEET_AXIS
        s_update, lr = self._s_update, self.server_lr
        scan_train = plane._scan_train
        guards = self.guards

        def sharded_seg(fleet_buf, g_flat, opt_state, gstate, cids, coefs,
                        evalid, batches, svalid, lstep):
            def step(carry, xs):
                buf, g, opt, gs = carry
                cid, cf, ev, b, sv = xs
                shard = cid // m_loc
                lrow = cid - shard * m_loc
                cur = jax.lax.dynamic_slice_in_dim(buf, lrow, 1, axis=0)
                mine = jax.lax.axis_index(ax) == shard
                # owning shard contributes its row via a one-row psum —
                # the fleet is never gathered (ShardedRowEngine's trick)
                row = jax.lax.psum(
                    jnp.where(mine, cur[0].astype(jnp.float32), 0.0), ax)
                if guards is None:
                    eve, row_eff = ev, row
                else:
                    # row is already the f32 gather — the exact operand
                    # guard_update would cast to, so verdicts match the
                    # unsharded paths
                    ok, row_eff, gs = _guards.guard_update(
                        guards, g, row, gs, ev)
                    eve = ev & ok
                if s_update is None:
                    if use_kernel:
                        g2 = kern(g, row_eff.astype(storage)[None], cf)
                    else:
                        g2 = (cf[0] * g.astype(jnp.float32)
                              + cf[1] * row_eff).astype(g.dtype)
                    if guards is not None:
                        g2 = jnp.where(eve, g2, g)
                else:
                    pg = cf[1] * (g.astype(jnp.float32) - row_eff)
                    g2, opt2 = s_update(g, pg, opt, lr)
                    g2 = jnp.where(eve, g2, g)
                    opt = jax.tree.map(
                        lambda a, o: jnp.where(eve, a, o), opt2, opt)
                if retrain:
                    new = scan_train(g2, b, sv, lstep)
                    write = jnp.where(eve & mine,
                                      new[None].astype(buf.dtype), cur)
                    buf = jax.lax.dynamic_update_slice_in_dim(
                        buf, write, lrow, axis=0)
                return (buf, g2, opt, gs), None
            (buf, g, opt, gs), _ = jax.lax.scan(
                step, (fleet_buf, g_flat, opt_state, gstate),
                (cids, coefs, evalid, batches, svalid))
            return buf, g, opt, gs

        rep = lambda t: jax.tree.map(lambda _: P(), t)   # noqa: E731
        gs_proto = () if guards is None else _guards.init_state(guards)
        in_specs = (fleet_buffer_spec(), P(), rep(opt_proto),
                    rep(gs_proto), P(), P(), P(), rep(batches_proto), P(),
                    P())
        out_specs = (fleet_buffer_spec(), P(), rep(opt_proto),
                     rep(gs_proto))
        f = jax.shard_map(sharded_seg, mesh=plane.mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)
        dn = (0, 1) if plane.donate else ()
        return jax.jit(f, donate_argnums=dn)

    def _prog_for(self, retrain: bool, batches, opt_state):
        if not self.sharded:
            # one jitted fn per retrain mode: jax.jit's own cache keys the
            # (shape, structure) variants, counted by ``variants()``
            key = ("seg", retrain, self._prog_ctx)
            if key not in self._progs:
                self._progs[key] = self._build_prog(retrain)
            return self._progs[key]
        key = ("sharded-seg", retrain, self._prog_ctx,
               jax.tree.structure(batches), jax.tree.structure(opt_state))
        if key not in self._progs:
            self._progs[key] = self._build_sharded_prog(
                retrain, batches, opt_state)
        return self._progs[key]

    # -- staging -------------------------------------------------------------
    def _stage_events(self, trace: EventTrace, start: int):
        with jax.profiler.TraceAnnotation(
                "afl.stage_events", seed=trace.base_seed,
                events=len(trace) - start):
            return stage_trace_events(self.plane, trace, start)

    def _segment_inputs(self, trace, staged, s0, s1, s_bucket):
        """:func:`segment_inputs` under its span."""
        with jax.profiler.TraceAnnotation(
                "afl.segment_inputs", seed=trace.base_seed, s0=s0, s1=s1,
                bucket=s_bucket):
            return segment_inputs(trace, staged, s0, s1, s_bucket,
                                  fedopt=self._s_update is not None)

    # -- execution -----------------------------------------------------------
    def _can_fold(self, trace) -> bool:
        """§III-B blend-only segments collapse to ONE closed-form MAC
        launch (``fold_sequential_blends``): the fleet rows are frozen
        between broadcasts, so the sequential eq. (3) chain is exactly
        c0·w + Σ_m cvec[m]·row_m.  Only when the per-event storage
        rounding is unobservable (f32) and the blend is a plain chain on
        one device — bf16 runs keep the scan so per-event rounding
        matches the reference loop bit-for-bit within test bounds."""
        return (not trace.per_event_retrain and self._s_update is None
                and not self.sharded and self.guards is None
                and np.dtype(self.base_engine.storage_dtype)
                == np.dtype(np.float32))

    def _run_folded(self, trace, s0, s1, fleet_buf, g_flat, opt_state,
                    gstate):
        c0, coefs = agg.fold_sequential_blends(trace.betas[s0:s1])
        cvec = np.zeros(trace.M, np.float64)
        # same-client repeats sum their folded mass (rows are constant
        # across the segment); dropped events have β=1 → zero mass
        np.add.at(cvec, trace.cids[s0:s1], coefs)
        if self.paged:
            # the M-wide MAC runs over the host arena, streamed P rows
            # at a time (uninitialized rows carry zero mass — their cid
            # never uploaded in this segment)
            self.launches += 1
            self.segments += 1
            g_flat = self.plane.fleet_weighted_sum(
                np.float32(c0), g_flat, cvec.astype(np.float32), fleet_buf)
            return fleet_buf, g_flat, opt_state, gstate
        key = ("fold", self._prog_ctx)
        if key not in self._progs:
            def fold(g, buf, c0_, cv):
                acc = (c0_ * g.astype(jnp.float32)
                       + jnp.tensordot(cv, buf.astype(jnp.float32), axes=1))
                return acc.astype(g.dtype)
            dn = (0,) if self.plane.donate else ()
            self._progs[key] = jax.jit(fold, donate_argnums=dn)
        self.launches += 1
        self.segments += 1
        g_flat = self._progs[key](g_flat, fleet_buf, np.float32(c0),
                                  cvec.astype(np.float32))
        return fleet_buf, g_flat, opt_state, gstate

    def _run_segment(self, trace, staged, s0, s1, s_bucket,
                     fleet_buf, g_flat, opt_state, gstate):
        retrain = trace.per_event_retrain
        if self._can_fold(trace):
            return self._run_folded(trace, s0, s1, fleet_buf, g_flat,
                                    opt_state, gstate)
        if not self.paged:
            cids, coefs, evalid, batches, svalid = self._segment_inputs(
                trace, staged, s0, s1, s_bucket)
            prog = self._prog_for(retrain, batches, opt_state)
            self.launches += 1
            self.segments += 1
            fleet_buf, g_flat, opt_state, gstate = prog(
                fleet_buf, g_flat, opt_state, gstate, cids, coefs, evalid,
                batches, svalid, self.plane._lstep)
            return fleet_buf, g_flat, opt_state, gstate
        # paged plane: sub-split so each launch addresses ≤ P distinct
        # clients, adopt the prefetch-staged rows, and remap the scan's
        # cid stream to slot indices (DESIGN.md §12).  Pad / non-resident
        # entries map to slot 0 — their evalid=False masks the retrain
        # write-back and their identity coefs make the blend a no-op, so
        # the slot-0 row's value never matters.
        plane = self.plane
        for t0, t1 in split_for_slots(trace.cids, s0, s1, plane.P):
            ccids = np.unique(trace.cids[t0:t1])
            fleet_buf = plane.adopt_chunk(fleet_buf, ccids)
            cids, coefs, evalid, batches, svalid = self._segment_inputs(
                trace, staged, t0, t1, s_bucket)
            slots = plane.store.slots_of(cids)
            cids = np.where(slots >= 0, slots, 0).astype(np.int32)
            prog = self._prog_for(retrain, batches, opt_state)
            self.launches += 1
            self.segments += 1
            fleet_buf, g_flat, opt_state, gstate = prog(
                fleet_buf, g_flat, opt_state, gstate, cids, coefs, evalid,
                batches, svalid, self.plane._lstep)
            if retrain:
                plane.store.mark_dirty(ccids)
        return fleet_buf, g_flat, opt_state, gstate

    def init_guard_state(self):
        """Fresh guard carry for this runner's config (``()`` when
        guards are off)."""
        return () if self.guards is None else _guards.init_state(self.guards)

    def run(self, trace: EventTrace, fleet_buf, g_flat, opt_state=(),
            guard_state=None, *, start: int = 0, eval_fn=None,
            eval_every: int = 10, hist=None, autosave_fn=None,
            autosave_every: Optional[int] = None, stop_flag=None):
        """Execute ``trace[start:]`` from the given device state.  Eval
        points and baseline broadcasts split the run into chunks (one
        launch per boundary action); everything between boundaries runs
        as bucket-grouped donated scan segments.  Returns the final
        ``(fleet_buf, g_flat, opt_state, guard_state)``.

        ``autosave_fn`` (called with ``{"fleet_buf", "g_flat",
        "opt_state", "guard_state", "cursor", "hist"}``) fires every
        ``autosave_every`` consumed events — but only at cursors where
        every boundary action (broadcast, eval) up to the cursor has
        already run, so a resume from the saved state replays nothing
        and skips nothing.  ``stop_flag`` (a nullary callable) is polled
        at the same points; when it reads true the runner saves and
        raises :class:`RunInterrupted`."""
        E = len(trace)
        gstate = guard_state if guard_state is not None \
            else self.init_guard_state()
        if start >= E:
            return fleet_buf, g_flat, opt_state, gstate
        if trace.per_event_retrain:
            staged = self._stage_events(trace, start)
        else:
            staged = None
            trace.s_buckets = np.zeros(E, np.int32)
        cuts = boundary_cuts(
            trace, start=start,
            eval_every=eval_every if eval_fn is not None else None)
        if self.paged:
            # lazy-init every uploader's row BEFORE prefetch staging —
            # the compiled trace names them all, so this is exact
            self.plane.warm_trace(trace.cids[start:])
        last_save = start

        def _save(cursor):
            nonlocal last_save
            if autosave_fn is not None:
                autosave_fn({"fleet_buf": fleet_buf, "g_flat": g_flat,
                             "opt_state": opt_state, "guard_state": gstate,
                             "cursor": int(cursor), "hist": hist})
            last_save = int(cursor)

        a = start
        for b in cuts:
            if b <= a:
                continue
            segs = group_segments(trace.s_buckets[a:b],
                                  min_run=self.min_run)
            if self.paged and not self._can_fold(trace):
                # exact prefetch: the async stager walks THIS chunk's
                # sub-segment plan (a boundary broadcast rewrites the
                # whole arena and cancels any plan, so plans don't span
                # cuts); each _run_segment adopt pops these in order.
                # Folded blend-only traces never touch the pool, so they
                # skip staging entirely.
                self.plane.store.plan([
                    np.unique(trace.cids[t0:t1])
                    for s0, s1, _ in segs
                    for t0, t1 in split_for_slots(
                        trace.cids, a + s0, a + s1, self.plane.P)])
            for s0, s1, bucket in segs:
                with jax.profiler.TraceAnnotation(
                        "afl.segment", seed=trace.base_seed, s0=a + s0,
                        s1=a + s1, bucket=bucket):
                    fleet_buf, g_flat, opt_state, gstate = \
                        self._run_segment(trace, staged, a + s0, a + s1,
                                          bucket, fleet_buf, g_flat,
                                          opt_state, gstate)
                cur = a + s1
                # mid-chunk cursors are safe save points: resume's
                # boundary_cuts(start=cur) re-derives every boundary
                # action at i >= cur, none of which has run yet
                if cur < b:
                    if stop_flag is not None and stop_flag():
                        _save(cur)
                        raise RunInterrupted(cur)
                    if autosave_every and cur - last_save >= autosave_every:
                        _save(cur)
            i = b - 1
            if trace.broadcast[i]:
                fleet_buf = self.plane.train_all(
                    g_flat, int(trace.seeds[i]))
                self.launches += 1
            if eval_fn is not None and trace.js[i] % eval_every == 0 \
                    and hist is not None:
                with jax.profiler.TraceAnnotation(
                        "afl.eval", seed=trace.base_seed,
                        upload=int(trace.js[i])):
                    got = eval_fn(self.engine.unflatten(g_flat))
                hist.add(float(trace.t_complete[i]), int(trace.js[i]), got)
            a = b
            # at a chunk boundary the save must come AFTER the boundary
            # actions: a cursor saved at b with the broadcast/eval still
            # pending would skip them both on resume
            if a < E:
                if stop_flag is not None and stop_flag():
                    _save(a)
                    raise RunInterrupted(a)
                if autosave_every and a - last_save >= autosave_every:
                    _save(a)
        return fleet_buf, g_flat, opt_state, gstate
