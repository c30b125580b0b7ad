"""Asynchronous federated learning loops (paper §II-B, §III).

Three AFL aggregation modes over the same event-driven scheduler:

* ``afl_alpha``    — §III-A: naive reuse of SFL's α as (1-β): demonstrates
  the geometric contribution decay (this is the *negative* result).
* ``afl_baseline`` — §III-B: strict-cycle scheduling + the triangular-solved
  β_j so that every M iterations reproduce one FedAvg round exactly.
* ``csmaafl``      — §III-C: fairness scheduling + eq. (11) staleness-aware
  coefficients (Algorithm 1).

The client fleet is simulated in virtual time; each client *physically*
holds its own model copy (as on a real edge fleet), so the server stores
only the current global model and the scalar staleness tracker — matching
the paper's storage argument against AsyncFedED.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core import faults as flt
from repro.core import guards as grd
from repro.core.agg_engine import engine_for
from repro.core.event_trace import RunInterrupted  # noqa: F401 (re-export)
from repro.core.scheduler import (AFLScheduler, BaselineAFLScheduler,
                                  ClientSpec, UploadEvent)
from repro.core.sfl import EvalFn, FLHistory, LocalTrainFn


def history_to_state(hist: Optional[FLHistory]) -> Optional[Dict[str, Any]]:
    """Dense-array view of an FLHistory so it can ride a checkpoint
    payload (``ckpt.save_afl_state``).  None when there is nothing to
    save."""
    if hist is None or not hist.times:
        return None
    keys = sorted(hist.metrics[0]) if hist.metrics else []
    return {"times": np.asarray(hist.times, np.float64),
            "iterations": np.asarray(hist.iterations, np.int64),
            "metrics": {k: np.asarray([m[k] for m in hist.metrics],
                                      np.float64) for k in keys}}


def history_from_state(state: Optional[Dict[str, Any]]) -> FLHistory:
    """Rebuild an FLHistory from :func:`history_to_state` output (or an
    empty one from None) — the resume side of the round-trip."""
    hist = FLHistory()
    if not state:
        return hist
    times = np.asarray(state.get("times", ()), np.float64)
    iters = np.asarray(state.get("iterations", ()), np.int64)
    metrics = state.get("metrics", {}) or {}
    for k in range(times.size):
        hist.add(float(times[k]), int(iters[k]),
                 {name: float(np.asarray(v)[k])
                  for name, v in metrics.items()})
    return hist


@dataclasses.dataclass
class AFLResult:
    params: Any
    history: FLHistory
    events: List[UploadEvent]
    betas: List[float]
    # plane runs also return the raw device state so a checkpoint can
    # round-trip the run mid-timeline (checkpoint/ckpt.save_afl_state):
    # {"fleet_buf", "g_flat", "opt_state", "cursor"} — cursor is the
    # number of trace events consumed (the resume point)
    state: Optional[Dict[str, Any]] = None
    # compiled-loop instrumentation ({"launches", "segments",
    # "variants"}) plus the fault/participation accounting under
    # ``stats["faults"]`` (``core.faults.participation_stats``) — present
    # on every path; dropped events are EXCLUDED from the per-client
    # participation tallies
    stats: Optional[Dict[str, Any]] = None


def run_afl(params0, fleet: Sequence[ClientSpec],
            local_train_fn: Optional[LocalTrainFn], *,
            algorithm: str,              # afl_alpha | afl_baseline | csmaafl
            iterations: int, tau_u: float, tau_d: float,
            gamma: float = 0.4, mu_momentum: float = 0.9,
            eval_fn: Optional[EvalFn] = None, eval_every: int = 10,
            server_opt: Optional[str] = None, server_lr: float = 1.0,
            max_staleness: Optional[int] = None,
            use_engine: bool = True,
            client_plane=None, use_client_plane: Optional[bool] = None,
            compiled_loop: Optional[bool] = None,
            resume_state: Optional[Dict[str, Any]] = None,
            faults=None, guards=None,
            autosave_every: Optional[int] = None,
            autosave_dir: Optional[str] = None,
            autosave_keep_last: Optional[int] = 3,
            stop_flag=None,
            seed: int = 0) -> AFLResult:
    """Legacy keyword entry point — a thin shim over the unified run
    API (``repro.api``): the keywords fold into a :class:`RunConfig`
    and expand back through ``cfg.afl_kwargs()`` into the same
    implementation ``repro.api.run(task, cfg)`` dispatches to, so both
    spellings are bit-identical by construction.  See
    :func:`_run_afl_impl` for the semantics of every knob.

    ``client_plane`` / ``use_client_plane`` / ``compiled_loop`` are
    deprecated here — select the plane and loop through ``RunConfig``
    (``repro.api.run``); explicit values warn but resolve to the same
    defaults the old signature had."""
    from repro.api import RunConfig, resolve_legacy_plane_kwargs
    client_plane, use_client_plane, compiled_loop = \
        resolve_legacy_plane_kwargs(
            "run_afl", client_plane=client_plane,
            use_client_plane=use_client_plane, compiled_loop=compiled_loop)
    cfg = RunConfig.from_afl_kwargs(
        algorithm=algorithm, iterations=iterations, tau_u=tau_u,
        tau_d=tau_d, gamma=gamma, mu_momentum=mu_momentum,
        eval_every=eval_every, server_opt=server_opt, server_lr=server_lr,
        max_staleness=max_staleness, use_engine=use_engine,
        use_client_plane=use_client_plane, compiled_loop=compiled_loop,
        faults=faults, guards=guards, autosave_every=autosave_every,
        autosave_dir=autosave_dir, autosave_keep_last=autosave_keep_last,
        seed=seed)
    return _run_afl_impl(params0, fleet, local_train_fn, eval_fn=eval_fn,
                         client_plane=client_plane,
                         resume_state=resume_state, stop_flag=stop_flag,
                         **cfg.afl_kwargs())


def _run_afl_impl(params0, fleet: Sequence[ClientSpec],
                  local_train_fn: Optional[LocalTrainFn], *,
                  algorithm: str,        # afl_alpha | afl_baseline | csmaafl
                  iterations: int, tau_u: float, tau_d: float,
                  gamma: float = 0.4, mu_momentum: float = 0.9,
                  eval_fn: Optional[EvalFn] = None, eval_every: int = 10,
                  server_opt: Optional[str] = None, server_lr: float = 1.0,
                  max_staleness: Optional[int] = None,
                  use_engine: bool = True,
                  client_plane=None, use_client_plane: bool = True,
                  compiled_loop: bool = False,
                  resume_state: Optional[Dict[str, Any]] = None,
                  faults=None, guards=None,
                  autosave_every: Optional[int] = None,
                  autosave_dir: Optional[str] = None,
                  autosave_keep_last: Optional[int] = 3,
                  stop_flag=None,
                  seed: int = 0) -> AFLResult:
    """Run one AFL variant.  One event == one global iteration (eq. 3).

    Three data planes, most fused first (all parity-tested to 1e-5):

    * ``client_plane`` (a ``core.client_plane.ClientPlane``, used when
      ``use_client_plane=True``): the whole fleet lives as one (M, n)
      device buffer; local SGD is one scanned launch per event and the
      blend ``dynamic_slice``s the uploader's row — ~2 launches per
      event total.  ``local_train_fn`` may be None in this mode.  A
      ``ShardedClientPlane`` runs the same loop with the buffer
      row-partitioned across a ``fleet`` device mesh (DESIGN.md §6) —
      this code path is identical; the plane and its shard-aware engine
      hide the placement.
    * ``use_engine=True`` (default, no plane): per-event fused flat-
      buffer blend through ``core.agg_engine``; local training stays the
      task's per-minibatch loop.
    * neither: the per-leaf ``aggregation.blend_pytree`` reference path.

    ``server_opt`` (beyond-paper, FedOpt-style): instead of the plain blend
    w ← β w + (1-β) w_m, treat Δ = (1-β)(w_m − w) as a pseudo-gradient and
    apply a server optimizer (e.g. "adam"): w ← ServerOpt(w, −Δ).  With
    server_opt=None this reduces exactly to eq. (3).  With the engine or
    plane active, the pseudo-gradient and the optimizer state live on the
    flat buffer (one fused delta launch, single-leaf optimizer pytree).

    ``max_staleness`` (beyond-paper, admission control): uploads staler
    than the bound are *dropped* — the client still receives the fresh
    global model (so it resynchronizes), but its update is not blended.
    eq. (11) already down-weights stale updates smoothly; the hard bound
    guards against pathological stragglers.

    ``compiled_loop=True`` (requires a client plane) lowers the WHOLE run
    through the event-trace compiler (``core.event_trace``, DESIGN.md
    §7): the scheduler timeline and every β_j are precomputed on the
    host, and the event loop executes as O(#buckets) jitted,
    buffer-donated ``lax.scan`` launches instead of a host hop per
    window — same history/params as the Python loop ≤1e-5.
    ``resume_state`` (a prior result's ``.state`` or
    ``ckpt.load_afl_state``) restarts a compiled run mid-timeline from
    its trace cursor.

    ``faults`` (``core.faults``: a ``FaultModel``, preset name, or
    kwargs dict) injects availability windows, mid-flight dropouts and
    flaky-uplink retries into the timeline before the loop consumes it.
    The realization is a pure function of the fault seed, so this
    reference loop, the compiled loop, the sharded plane and run-stacked
    sweeps see bit-identical drop patterns and realized staleness.
    Fault-dropped events are no-ops (no tracker update, no blend, no
    retrain — the client keeps its stale model); deferred/retried events
    carry retry-inflated staleness into eq. (11).

    ``guards`` (``core.guards``: a ``GuardConfig``, preset name, True,
    or kwargs dict; requires a client plane) arms the in-scan update
    guards: non-finite rows and update-norm outliers are rejected as
    identity steps — no model advance, no retrain write-back — with the
    SAME float32 decision expression on the windowed, compiled, sharded
    and sweep paths; rejection counters land in ``stats["faults"]``
    (``guard_rejects`` / ``guard_nonfinite`` / ``guard_norm_outliers`` /
    ``guard_clipped``).  The β replay and staleness tracker are
    metadata-derived and unperturbed by rejections (DESIGN.md §10).

    ``autosave_every`` + ``autosave_dir`` (plane runs, windowed or
    compiled) periodically write crash-safe checkpoints
    (``ckpt.save_afl_state`` → ``autosave_dir/state-<cursor>.ckpt``,
    rotated to ``autosave_keep_last``); ``resume_state`` restarts either
    loop mid-timeline from such a checkpoint (the windowed loop
    fast-forwards the host-side coefficient bookkeeping and resumes the
    device work at the cursor — histories and final params match the
    uninterrupted run).  ``stop_flag`` (nullary callable) requests a
    graceful stop: the loop writes one final consistent autosave and
    raises :class:`RunInterrupted`.
    """
    M = len(fleet)
    alpha = agg.sfl_alpha([c.num_samples for c in fleet])
    plane = client_plane if (use_client_plane and client_plane is not None) \
        else None
    if plane is None and local_train_fn is None:
        raise ValueError("local_train_fn is required without a client plane")
    s_init = s_update = None
    if server_opt is not None:
        from repro.optim import optimizers as _opt
        s_init, s_update = _opt.get_optimizer(server_opt)

    gcfg = grd.resolve_guards(guards)
    if plane is None:
        if gcfg is not None:
            raise ValueError("guards require a client plane")
        if autosave_dir is not None or resume_state is not None:
            raise ValueError("autosave/resume require a client plane")
    if (autosave_every is not None) != (autosave_dir is not None):
        raise ValueError("autosave_every and autosave_dir go together")

    # a windowed autosave tags its state with ``windowed`` — resuming it
    # re-enters THIS loop; untagged (compiled) states resume compiled
    windowed_resume = (resume_state is not None
                       and bool(resume_state.get("windowed")))
    if compiled_loop or (resume_state is not None and not windowed_resume):
        if plane is None:
            raise ValueError("compiled_loop requires a client plane")
        return _run_compiled(params0, fleet, plane, algorithm=algorithm,
                             iterations=iterations, tau_u=tau_u,
                             tau_d=tau_d, gamma=gamma,
                             mu_momentum=mu_momentum, eval_fn=eval_fn,
                             eval_every=eval_every, server_opt=server_opt,
                             server_lr=server_lr, s_init=s_init,
                             max_staleness=max_staleness,
                             resume_state=resume_state, faults=faults,
                             guards=gcfg, autosave_every=autosave_every,
                             autosave_dir=autosave_dir,
                             autosave_keep_last=autosave_keep_last,
                             stop_flag=stop_flag, seed=seed)

    if algorithm == "afl_baseline":
        sched = BaselineAFLScheduler(fleet, tau_u=tau_u, tau_d=tau_d)
        order = sched.cycle_order()
        cycle_betas = agg.solve_betas(alpha, order)   # eqs. (9)-(10)
    elif algorithm in ("afl_alpha", "csmaafl"):
        sched = AFLScheduler(fleet, tau_u=tau_u, tau_d=tau_d)
    else:
        raise ValueError(f"unknown AFL algorithm '{algorithm}'")

    tracker = agg.StalenessTracker(momentum=mu_momentum)
    global_params = params0
    engine = g_flat = fleet_buf = opt_state = None
    start = 0
    paged = getattr(plane, "paged", False)
    wguard = None if gcfg is None else grd.WindowedGuard(plane, gcfg)
    if plane is not None:
        # fleet-resident mode: global model AND every client model live
        # as flat device buffers; pytrees materialize only for eval
        engine = plane.engine
        global_params = None
        if windowed_resume:
            g_flat = resume_state["g_flat"]
            fleet_buf = resume_state["fleet_buf"]
            if paged:
                # the checkpointed (P, n) pool is only meaningful with
                # its slot table + arena — both live in the spilled store
                if resume_state.get("fleet_store") is None:
                    raise ValueError(
                        "resume state has no fleet_store payload — it was "
                        "saved by a dense plane and cannot resume paged")
                plane.load_store_state(resume_state["fleet_store"])
            opt_state = (resume_state.get("opt_state", ())
                         if server_opt is not None else None)
            start = int(resume_state["cursor"])
            if start > iterations:
                raise ValueError(
                    f"resume cursor {start} beyond the {iterations}-event "
                    "run — was the run saved with fewer iterations?")
            if wguard is not None \
                    and resume_state.get("guard_state") is not None:
                import jax as _jax
                wguard.state = _jax.tree.map(jnp.asarray,
                                             resume_state["guard_state"])
        else:
            g_flat = engine.flatten(params0)
            if server_opt is not None:
                opt_state = s_init(g_flat)
            # every client immediately trains on the initial broadcast
            # w_0 — ONE vmapped launch over the (M, n) buffer
            fleet_buf = plane.init_fleet(g_flat, seed * 100003)
    else:
        if use_engine:
            # the global model lives in the engine's contiguous flat
            # buffer between events; each event is one fused launch
            engine = engine_for(params0)
            g_flat = engine.flatten(params0)
            if server_opt is not None:
                opt_state = s_init(g_flat)
        elif server_opt is not None:
            opt_state = s_init(params0)
        # every client immediately trains on the initial broadcast w_0
        client_models: Dict[int, Any] = {}
        for c in fleet:
            client_models[c.cid] = local_train_fn(
                params0, c.cid, c.local_steps, seed * 100003)

    def cur_params():
        return engine.unflatten(g_flat) if global_params is None \
            else global_params

    # --- event-window retrain batching (plane mode) ---------------------
    # A client's retrain is only consumed at its NEXT upload, so retrains
    # for a window of events with distinct uploaders are independent:
    # buffer (cid, g-snapshot, K, seed) and flush them as ONE vmapped
    # launch when a cid repeats, when the window hits the plane's
    # ``window_cap`` (bounds the per-event g-snapshot memory on M≥1000
    # fleets), or at loop end.  A sharded plane additionally groups the
    # flushed window by owning shard so every shard retrains its own
    # slice concurrently (DESIGN.md §6).  Blends stay sequential (they
    # are the cheap part); histories are bit-identical to the per-event
    # order.
    pending: List[tuple] = []
    pending_cids = set()

    def flush_pending():
        nonlocal fleet_buf
        if pending:
            fleet_buf = plane.train_rows(fleet_buf, pending)
            pending.clear()
            pending_cids.clear()

    def queue_retrain(cid, steps, seed_j):
        # snapshot survives the next blend's buffer donation (TPU/GPU)
        snap = jnp.copy(g_flat) if engine.donate else g_flat
        pending.append((cid, snap, steps, seed_j))
        pending_cids.add(cid)
        cap = getattr(plane, "window_cap", None)
        if cap is not None and len(pending) >= cap:
            flush_pending()

    hist = history_from_state(resume_state.get("history")) \
        if windowed_resume else FLHistory()
    events: List[UploadEvent] = []
    betas: List[float] = []
    stale_flags: List[bool] = []
    if eval_fn is not None and start == 0 and not hist.times:
        hist.add(0.0, 0, eval_fn(cur_params()))

    # fault injection: realize the timeline ONCE (same transform the
    # event-trace compiler applies, keyed by the same seed — the drop
    # pattern and realized staleness are bit-identical to the compiled
    # paths); without faults the scheduler generator streams lazily
    fm = flt.resolve_faults(faults)
    if fm is not None and fm.active():
        event_stream = flt.realize_events(
            sched.trace(iterations), fm, algorithm=algorithm, M=M,
            tau_u=tau_u, seed=seed).events
    else:
        event_stream = sched.events(iterations)

    def snapshot_state(cursor: int) -> Dict[str, Any]:
        st = {"fleet_buf": fleet_buf, "g_flat": g_flat,
              "opt_state": opt_state if opt_state is not None else (),
              "cursor": cursor, "windowed": True}
        if paged:
            st["fleet_store"] = plane.store_state(fleet_buf)
        if wguard is not None:
            st["guard_state"] = wguard.state
        h = history_to_state(hist)
        if h is not None:
            st["history"] = h
        return st

    last_save = start
    for idx, ev in enumerate(event_stream):
        # resume fast-forward: events before the cursor replay ONLY the
        # host-side coefficient bookkeeping (the staleness tracker is a
        # scalar recurrence over the metadata stream) — the device state
        # they produced came back from the checkpoint
        replay = idx < start
        events.append(ev)
        accepted = ev.outcome == flt.OUTCOME_OK
        if not accepted:
            # fault-dropped upload: the server never sees it — no
            # tracker update, no blend, no retrain (the client keeps its
            # stale model and its last version i); the §III-B broadcast
            # and the eval cadence still fire on schedule below
            betas.append(1.0)
            stale_flags.append(False)
        else:
            # ---- choose the aggregation coefficient ----
            if algorithm == "afl_alpha":
                one_minus_beta = float(alpha[ev.cid])      # §III-A naive
            elif algorithm == "afl_baseline":
                pos_in_cycle = (ev.j - 1) % M
                one_minus_beta = 1.0 - float(cycle_betas[pos_in_cycle])
            else:  # csmaafl, eq. (11)
                mu = tracker.update(ev.staleness)
                one_minus_beta = agg.staleness_coefficient(
                    ev.j, ev.i, mu, gamma)
            stale = (max_staleness is not None
                     and ev.staleness > max_staleness)
            stale_flags.append(stale)
            if stale:
                one_minus_beta = 0.0      # admission control: drop update
            beta = 1.0 - one_minus_beta
            betas.append(beta)

            # ---- eq. (3): w_{j+1} = β w_j + (1-β) w_i^m ----
            guard_ok, row_eff = True, None
            if replay:
                pass
            elif plane is not None:
                if ev.cid in pending_cids:
                    # this uploader's pending retrain feeds this blend
                    flush_pending()
                if paged:
                    # page the uploader's row in BEFORE guard/blend so
                    # the slot-addressed expressions below resolve it
                    fleet_buf = plane.ensure_resident(fleet_buf, [ev.cid])
                if wguard is not None:
                    guard_ok, row_eff = wguard.check(g_flat, fleet_buf,
                                                     ev.cid)
                clip = (wguard is not None
                        and wguard.cfg.clip_norm is not None)
                if not guard_ok:
                    # in-scan reject, host-driven: identity step — no
                    # model advance, no opt advance, no retrain below
                    # (DESIGN.md §10); β bookkeeping above is untouched
                    pass
                elif server_opt is None:
                    if clip:
                        g_flat = wguard.blend(g_flat, row_eff, beta)
                    else:
                        g_flat = engine.blend_row_flat(g_flat, fleet_buf,
                                                       ev.cid, beta)
                else:
                    if clip:
                        pg = wguard.delta(g_flat, row_eff, one_minus_beta)
                    else:
                        pg = engine.delta_row_flat(g_flat, fleet_buf,
                                                   ev.cid, one_minus_beta)
                    g_flat, opt_state = s_update(g_flat, pg, opt_state,
                                                 server_lr)
            elif server_opt is None:
                if engine is not None:
                    g_flat, global_params = engine.blend_flat(
                        g_flat, client_models[ev.cid], beta)
                else:
                    global_params = agg.blend_pytree(
                        global_params, client_models[ev.cid], beta)
            elif engine is not None:
                # pseudo-gradient −Δ on the flat buffer (one fused
                # launch), server optimizer over the single-leaf pytree
                pg = engine.delta_flat(g_flat, client_models[ev.cid],
                                       one_minus_beta)
                g_flat, opt_state = s_update(g_flat, pg, opt_state,
                                             server_lr)
                global_params = engine.unflatten(g_flat)
            else:
                # per-leaf reference path for the server optimizer
                import jax as _jax
                import jax.numpy as _jnp
                pseudo_grad = _jax.tree.map(
                    lambda g, c: (1.0 - beta) * (g.astype(_jnp.float32)
                                                 - c.astype(_jnp.float32)),
                    global_params, client_models[ev.cid])
                global_params, opt_state = s_update(
                    global_params, pseudo_grad, opt_state, server_lr)

            # ---- §II-B: only the uploader receives w_{j+1} (eq. 4) ----
            if not replay and guard_ok and algorithm != "afl_baseline":
                if plane is not None:
                    queue_retrain(ev.cid, ev.local_steps,
                                  seed * 100003 + ev.j)
                else:
                    client_models[ev.cid] = local_train_fn(
                        global_params, ev.cid, ev.local_steps,
                        seed * 100003 + ev.j)

        if replay:
            continue

        # ---- §III-B requirement (c): broadcast to *all* clients every
        # M iterations (fires on schedule even if this slot dropped);
        # mid-cycle, clients keep training from the cycle-start model.
        if algorithm == "afl_baseline" and ev.j % M == 0:
            if plane is not None:
                fleet_buf = plane.train_all(g_flat, seed * 100003 + ev.j)
            else:
                for c in fleet:
                    client_models[c.cid] = local_train_fn(
                        global_params, c.cid, c.local_steps,
                        seed * 100003 + ev.j)

        if eval_fn is not None and ev.j % eval_every == 0:
            hist.add(ev.t_complete, ev.j, eval_fn(cur_params()))

        # ---- crash-safe autosave + graceful stop (plane runs) --------
        if plane is not None and (autosave_dir is not None
                                  or stop_flag is not None):
            cursor = idx + 1
            want_stop = stop_flag is not None and stop_flag()
            want_save = (autosave_dir is not None and autosave_every
                         and cursor - last_save >= autosave_every
                         and cursor < iterations)
            if want_stop or want_save:
                # pending retrain snapshots were taken at queue time, so
                # flushing early is value-identical to flushing late
                flush_pending()
                if autosave_dir is not None:
                    from repro.checkpoint import ckpt as _ckpt
                    _ckpt.save_afl_state(
                        _ckpt.autosave_path(autosave_dir, cursor),
                        snapshot_state(cursor), step=cursor,
                        keep_last=autosave_keep_last,
                        metadata={"algorithm": algorithm,
                                  "loop": "windowed"})
                last_save = cursor
                if want_stop:
                    raise RunInterrupted(cursor)
    if plane is not None:
        flush_pending()       # leave the fleet buffer fully retrained
    state = None
    if plane is not None:
        state = snapshot_state(len(events))
    stats = {"faults": flt.participation_stats(
        [e.cid for e in events], betas,
        [e.outcome != flt.OUTCOME_OK for e in events], stale_flags, M,
        attempts=[e.attempts for e in events],
        outcomes=[e.outcome for e in events],
        staleness=[e.staleness for e in events],
        guards=None if wguard is None else wguard.counts())}
    stats.update(plane.memory_stats() if plane is not None
                 else {"peak_device_rows": M, "prefetch_stalls": 0})
    return AFLResult(cur_params(), hist, events, betas, state, stats)


def _run_compiled(params0, fleet, plane, *, algorithm, iterations, tau_u,
                  tau_d, gamma, mu_momentum, eval_fn, eval_every,
                  server_opt, server_lr, s_init, max_staleness,
                  resume_state, faults, seed, guards=None,
                  autosave_every=None, autosave_dir=None,
                  autosave_keep_last=3, stop_flag=None) -> AFLResult:
    """The ``compiled_loop=True`` body: compile the whole timeline once,
    then execute it as bucket-grouped donated scan segments
    (``core.event_trace``, DESIGN.md §7).  Guards ride the scan carry;
    autosaves fire at segment boundaries through the runner's
    ``autosave_fn`` hook (DESIGN.md §10)."""
    from repro.core import event_trace as _et

    with jax.profiler.TraceAnnotation("afl.compile_trace", seed=seed):
        trace = _et.compile_afl_trace(
            fleet, algorithm=algorithm, iterations=iterations, tau_u=tau_u,
            tau_d=tau_d, gamma=gamma, mu_momentum=mu_momentum,
            max_staleness=max_staleness, faults=faults, seed=seed)
    runner = _et.CompiledLoopRunner(plane, server_opt=server_opt,
                                    server_lr=server_lr, guards=guards)
    engine = plane.engine
    paged = getattr(plane, "paged", False)
    if resume_state is None:
        hist = FLHistory()
        g_flat = engine.flatten(params0)
        opt_state = s_init(g_flat) if server_opt is not None else ()
        guard_state = runner.init_guard_state()
        # every client trains on the initial broadcast w_0 — ONE launch
        fleet_buf = plane.init_fleet(g_flat, seed * 100003)
        runner.count_launch()
        start = 0
        if eval_fn is not None:
            with jax.profiler.TraceAnnotation("afl.eval", seed=seed,
                                              upload=0):
                hist.add(0.0, 0, eval_fn(params0))
    else:
        hist = history_from_state(resume_state.get("history"))
        g_flat = resume_state["g_flat"]
        fleet_buf = resume_state["fleet_buf"]
        if paged:
            if resume_state.get("fleet_store") is None:
                raise ValueError(
                    "resume state has no fleet_store payload — it was "
                    "saved by a dense plane and cannot resume paged")
            plane.load_store_state(resume_state["fleet_store"])
        opt_state = resume_state.get("opt_state", ())
        guard_state = resume_state.get("guard_state")
        if guard_state is None:
            guard_state = runner.init_guard_state()
        start = int(resume_state["cursor"])
        if start > len(trace):
            raise ValueError(
                f"resume cursor {start} beyond the {len(trace)}-event "
                "trace — was the run compiled with fewer iterations?")
        if start == 0 and not hist.times and eval_fn is not None:
            with jax.profiler.TraceAnnotation("afl.eval", seed=seed,
                                              upload=0):
                hist.add(0.0, 0, eval_fn(engine.unflatten(g_flat)))

    autosave_fn = None
    if autosave_dir is not None:
        from repro.checkpoint import ckpt as _ckpt

        def autosave_fn(st):
            sd = {"fleet_buf": st["fleet_buf"], "g_flat": st["g_flat"],
                  "opt_state": st["opt_state"], "cursor": st["cursor"]}
            if paged:
                sd["fleet_store"] = plane.store_state(st["fleet_buf"])
            if runner.guards is not None:
                sd["guard_state"] = st["guard_state"]
            h = history_to_state(st["hist"])
            if h is not None:
                sd["history"] = h
            _ckpt.save_afl_state(
                _ckpt.autosave_path(autosave_dir, st["cursor"]), sd,
                step=st["cursor"], keep_last=autosave_keep_last,
                metadata={"algorithm": algorithm, "loop": "compiled"})

    fleet_buf, g_flat, opt_state, guard_state = runner.run(
        trace, fleet_buf, g_flat, opt_state, guard_state, start=start,
        eval_fn=eval_fn, eval_every=eval_every, hist=hist,
        autosave_fn=autosave_fn, autosave_every=autosave_every,
        stop_flag=stop_flag)
    state = {"fleet_buf": fleet_buf, "g_flat": g_flat,
             "opt_state": opt_state, "cursor": len(trace)}
    if paged:
        state["fleet_store"] = plane.store_state(fleet_buf)
    gcounts = None
    if runner.guards is not None:
        state["guard_state"] = guard_state
        gcounts = grd.state_counts(guard_state)
    h = history_to_state(hist)
    if h is not None:
        state["history"] = h
    stats = {"launches": runner.launches, "segments": runner.segments,
             "variants": runner.variants(),
             "faults": flt.trace_stats(trace, guards=gcounts)}
    stats.update(plane.memory_stats())
    return AFLResult(engine.unflatten(g_flat), hist, trace.events[start:],
                     [float(b) for b in trace.betas[start:]], state, stats)
