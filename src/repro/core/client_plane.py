"""Fused client-fleet training plane — the client-side data plane.

PR 1 made every *server* blend one fused launch; the hot path then moved
to the clients: each upload event still paid O(K·local_batches) separate
jit dispatches for local SGD, a host→device transfer per minibatch, and
a per-leaf re-flatten of the uploading client's pytree at blend time.
This module removes all three (docs/DESIGN.md §4):

* **Fleet buffer** — the ENTIRE fleet's models live as ONE device-
  resident ``(M, n)`` stacked flat buffer sharing ``AggEngine``'s
  ravel/unravel plans.  Client m's model is row m; the server blend
  ``dynamic_slice``s the row out (``AggEngine.blend_row_flat``), so no
  pytree is ever materialized on the event path.
* **Scanned local SGD** — a client's K·B minibatches for one round are
  staged as one device array up front and consumed by ``lax.scan``: ONE
  dispatch per ``local_train`` call instead of one per minibatch.  The
  scan carries the client model as the engine's TREE (one array per
  leaf, ``AggEngine.split_expr``), so the flat row is split once before
  the first step and joined once after the last: a flat carry would pay
  a slice, a reshape and a concatenate of the whole row (on TPU, a
  relayout) at every step.
* **Vmapped rounds** — FedAvg rounds (and the baseline-AFL every-M
  broadcast) ``vmap`` the tree scan across all M clients: a whole round
  of fleet-wide local training is ONE launch, which splits the global,
  trains and writes each client's leaves into its row of the (M, n)
  buffer (``AggEngine.flatten_rows_expr``).  Event windows, run-batched
  sweeps and the sharded plane's rounds share that expression.
* **Pow2 bucketing** — batch counts are bucketed to the next power of
  two (padded steps carry a ``valid=False`` mask and leave the row
  untouched), so a fleet whose K_m varies 1..K compiles at most
  log2(K·B) scan variants instead of one per distinct batch count.
* **Donation** — on TPU/GPU the fleet buffer is donated across
  ``train_row`` calls, so the row update is in-place at the XLA level.

The plane is constructed by a task (``CNNTask.client_plane`` /
``LMTask.client_plane``) from two callables:

``step_fn(params_tree, batch) -> params_tree``
    one minibatch of local SGD on the engine's tree: the template's
    structure and leaf shapes, each leaf in the ROW's dtype (the storage
    dtype).  The step casts to the model's own dtypes inside its loss
    (``AggEngine.cast_expr``) and returns its update in the row's dtype,
    so every step rounds through the storage dtype exactly as the flat
    row did (a no-op where storage and leaves are both f32).  A
    single-leaf template's tree is the (n,) row itself.  Traced inside
    ``lax.scan`` — must be jax-pure.  A task whose step reads device
    arrays (its staged training set) passes a ``jax.tree_util.Partial``
    binding them: every plane program takes the step as its LAST
    argument, so those arrays enter the compiled programs as arguments.
    Closed over instead, XLA would embed them as constants in every
    program variant (the paper CNN's 188 MB image set, once per
    variant: slow compiles, executables too large to cache).
``batch_fn(cid, num_steps, seed) -> pytree of np arrays``
    the client's staged minibatches for one round, every leaf with
    leading axis = number of minibatches.  Must draw the SAME batch
    sequence as the task's per-minibatch ``local_train_fn`` so the
    plane-on/plane-off parity holds to 1e-5.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.tree_util import Partial

from repro.core.agg_engine import AggEngine, _can_donate, pow2_bucket
from repro.core.scheduler import ClientSpec

StepFn = Callable[[Any, Any], Any]
BatchFn = Callable[[int, int, int], Any]


def _num_batches(batches) -> int:
    return int(jax.tree.leaves(batches)[0].shape[0])


def _pad_batches(batches, bucket: int):
    """Zero-pad every leaf's leading axis to ``bucket`` steps."""
    def pad(x):
        x = np.asarray(x)
        short = bucket - x.shape[0]
        if short <= 0:
            return x
        return np.concatenate(
            [x, np.zeros((short,) + x.shape[1:], x.dtype)])
    return jax.tree.map(pad, batches)


class ClientPlane:
    """Device-resident (M, n) client-state matrix + fused local training.

    ``engine`` fixes the flat layout (shared with the server blends);
    ``fleet`` fixes M and each client's default K_m.  ``bucket=False``
    disables pow2 bucketing (compile one scan variant per distinct batch
    count — only sensible for fixed-K microbenchmarks).
    """

    def __init__(self, engine: AggEngine, fleet: Sequence[ClientSpec],
                 step_fn: StepFn, batch_fn: BatchFn, *,
                 bucket: bool = True, donate: Optional[bool] = None,
                 unroll: Optional[int] = None):
        self.engine = engine
        self.fleet = list(fleet)
        self.M = len(self.fleet)
        # row m of the fleet buffer IS client m's model; a reordered or
        # sub-sampled fleet would make the row blends address the wrong
        # client (dynamic_slice CLAMPS out-of-range indices, silently)
        if any(c.cid != i for i, c in enumerate(self.fleet)):
            raise ValueError("client plane requires fleet[i].cid == i "
                             "(rows are addressed by cid)")
        self.step_fn = step_fn
        # the step as a pytree argument of every plane program (see the
        # module docstring); a plain function becomes a leafless Partial
        self._lstep = (step_fn if isinstance(step_fn, Partial)
                       else Partial(step_fn))
        self.batch_fn = batch_fn
        self.bucket = bucket
        # per-client batch sizes (ClientSpec.batch_size): the SAMPLE axis
        # (axis 1 of every staged leaf) pads to one fleet-wide pow2
        # bucket with a sample-valid mask, so heterogeneous B_m share a
        # single compiled program; step_fn then receives
        # {"batch": ..., "sample_valid": (B_pad,) bool} per scan step
        declared = [getattr(c, "batch_size", None) for c in self.fleet]
        if any(b is not None for b in declared):
            if any(b is None for b in declared):
                raise ValueError(
                    "per-client batch sizes must be declared on every "
                    "client or none (ClientSpec.batch_size)")
            if not getattr(step_fn, "supports_sample_mask", False):
                # fail at plane-build time with a clear message, not at
                # trace time inside jit when step_fn indexes the staged
                # {"batch", "sample_valid"} dict it doesn't expect
                raise ValueError(
                    "fleet declares per-client batch sizes but step_fn "
                    "does not set supports_sample_mask=True — it must "
                    "consume {'batch', 'sample_valid'} staged trees and "
                    "mask its per-sample loss (see CNNTask.client_plane)")
            self.sample_pad: Optional[int] = pow2_bucket(max(declared))
        else:
            self.sample_pad = None
        # cap on the AFL event-window length before a forced retrain
        # flush (None = only flush on uploader repeat); large fleets set
        # this to bound the pending g-snapshot memory (one (n,) buffer
        # per queued event)
        self.window_cap: Optional[int] = None
        donate = _can_donate() if donate is None else donate
        self.donate = donate
        if unroll is None:
            # XLA:CPU executes while-loop bodies on a slow path (~4x on
            # the paper CNN), so fully unroll the scan there — the pow2
            # bucketing bounds the number of unrolled program variants.
            # On TPU/GPU keep the rolled scan (compact programs, loop
            # bodies run at full speed).
            unroll = True if jax.default_backend() == "cpu" else 1
        self.unroll = unroll

        # the rows are in the storage dtype, so the split tree is too and
        # the joins' casts back to it are no-ops
        split, join = engine.split_expr, engine.flatten_expr
        join_rows = engine.flatten_rows_expr

        def scan_tree(params, batches, valid, lstep):
            """Local SGD over one model in the engine's tree form."""
            def body(p, xs):
                b, v = xs
                p2 = lstep(p, b)
                return jax.tree.map(
                    lambda a, o: jnp.where(v, a.astype(o.dtype), o),
                    p2, p), None
            out, _ = jax.lax.scan(body, params, (batches, valid),
                                  unroll=unroll)
            return out

        def scan_train(flat, batches, valid, lstep):
            """Local SGD over one flat row: one program, KB fused steps,
            the model carried per leaf between the first and last."""
            return join(scan_tree(split(flat), batches, valid, lstep))

        def fleet_train(out, gs, batches, valid, lstep, g_axis=None):
            """C clients' local SGD inside one program: the shared global
            (``g_axis`` None) or the C globals (``g_axis`` 0) split, the
            tree scan vmapped over the clients, and every client's model
            written leaf by leaf into the (C, n) rows ``out``.  Joined
            by a concatenate instead, the TPU compiler kept the paper
            CNN's conv1 weight carry in on-chip memory and tiled its
            gradient smaller, summing it in another order than a plain
            vmapped per-leaf round (``chipbench/reference.py``): the
            rows then drift from it by rounding (M = 100, v5e)."""
            params = split(gs) if g_axis is None else jax.vmap(split)(gs)
            trees = jax.vmap(scan_tree, in_axes=(g_axis, 0, 0, None))(
                params, batches, valid, lstep)
            return join_rows(out, trees)

        def new_rows(c):
            return jnp.zeros((c, engine.n), engine.storage_dtype)

        self._scan_train = scan_train          # subclasses re-map this
        self._fleet_train = fleet_train
        self._new_rows = new_rows
        self._train_flat = jax.jit(scan_train)

        def train_row(fleet_buf, g_flat, cid, batches, valid, lstep):
            new = scan_train(g_flat, batches, valid, lstep)
            return jax.lax.dynamic_update_slice_in_dim(
                fleet_buf, new[None], cid, axis=0)

        self._train_row = jax.jit(
            train_row, donate_argnums=(0,) if donate else ())
        # the fleet round writes into a fresh (M, n) buffer it is handed
        # (donated): the rows' join then updates it in place.  A lambda:
        # the benchmark's fleet_round_ms reads its program, jit__lambda
        self._train_all = jax.jit(
            lambda out, g_flat, batches, valid, lstep: fleet_train(
                out, g_flat, batches, valid, lstep),
            donate_argnums=(0,) if donate else ())

        def train_rows(fleet_buf, gs, cids, batches, valid, lstep):
            rows = fleet_train(new_rows(cids.shape[0]), gs, batches,
                               valid, lstep, g_axis=0)          # (W, n)
            return fleet_buf.at[cids].set(rows)

        self._train_rows = jax.jit(
            train_rows, donate_argnums=(0,) if donate else ())

        # RUN-BATCHED fleet round (the sweep plane's init/broadcast path,
        # docs/DESIGN.md §8): R independent runs' fleet-wide rounds as ONE
        # launch over (R, n) globals and (R, M, S, ...) staged batches —
        # ``lax.map`` over the run axis of the fleet round (jit's own
        # cache keys the batch-tree structure/shape variants).
        # A vmap over runs of the vmap over clients compiles the paper
        # CNN's R=4, M=100 round for a TPU v5e in 11 minutes; the mapped
        # fleet round compiles once, like ``train_all``.
        def train_all_runs_body(g_flats, batches, valid, lstep):
            c = valid.shape[1]
            return jax.lax.map(
                lambda a: fleet_train(new_rows(c), *a, lstep),
                (g_flats, batches, valid))

        self._train_all_runs = jax.jit(train_all_runs_body)

    # -- staging ------------------------------------------------------------
    def _bucketed(self, nb: int) -> int:
        if nb <= 0:
            raise ValueError("a training round needs at least one batch")
        return pow2_bucket(nb) if self.bucket else nb

    def _staged_batches(self, cid: int, num_steps: int, seed: int):
        """``batch_fn`` + the per-client sample-axis padding policy.

        With heterogeneous ``ClientSpec.batch_size`` declared, every
        leaf's sample axis (axis 1) zero-pads to the fleet-wide pow2
        bucket and the staged tree becomes ``{"batch": <padded tree>,
        "sample_valid": (S, B_pad) bool}`` — the scan then feeds
        ``step_fn`` one ``{"batch": ..., "sample_valid": ...}`` slice per
        step, and the task masks its per-sample loss accordingly
        (docs/DESIGN.md §4).  Without declarations this is ``batch_fn``
        verbatim, so uniform fleets (and the toy parity tasks whose axis
        1 is a feature dim) are untouched."""
        b = self.batch_fn(cid, num_steps, seed)
        if self.sample_pad is None:
            return b
        B = self.fleet[cid].batch_size
        leaves = jax.tree.leaves(b)
        if any(x.shape[1] != B for x in leaves):
            raise ValueError(
                f"client {cid} staged batches carry sample axis "
                f"{leaves[0].shape[1]} != declared batch_size {B}")
        pad = self.sample_pad - B

        def padx(x):
            x = np.asarray(x)
            if pad <= 0:
                return x
            widths = [(0, 0)] * x.ndim
            widths[1] = (0, pad)
            return np.pad(x, widths)

        mask = np.zeros((leaves[0].shape[0], self.sample_pad), bool)
        mask[:, :B] = True
        return {"batch": jax.tree.map(padx, b), "sample_valid": mask}

    def _stage_one(self, cid: int, num_steps: int, seed: int,
                   bucket: Optional[int] = None):
        batches = self._staged_batches(cid, num_steps, seed)
        nb = _num_batches(batches)
        bucket = self._bucketed(nb) if bucket is None else bucket
        valid = np.arange(bucket) < nb
        return _pad_batches(batches, bucket), valid

    def backpressure_cap(self, max_batch: int) -> int:
        """Admission bound for the streaming ingest plane (DESIGN.md
        §11): the configured ``window_cap`` when set — backpressure and
        the windowed loop's event-window bound are the same knob — else
        a few micro-batches of headroom."""
        if self.window_cap is not None:
            return int(self.window_cap)
        return max(4 * int(max_batch), 64)

    # -- memory accounting (DESIGN.md §12) -----------------------------------
    paged = False

    def memory_stats(self) -> dict:
        """Device-residency counters for run stats: the dense plane
        keeps all M rows resident and never prefetches."""
        return {"peak_device_rows": self.M, "prefetch_stalls": 0}

    # -- fused local training -----------------------------------------------
    def init_fleet(self, g_flat: jnp.ndarray, seed: int) -> jnp.ndarray:
        """Every client trains from the initial broadcast w_0: one vmapped
        launch producing the (M, n) fleet buffer."""
        return self.train_all(g_flat, seed)

    def _stage_fleet(self, seed: int,
                     local_steps_override: Optional[int] = None):
        """Stage one round of batches for the WHOLE fleet: stacked
        (M, bucket, ...) leaves + the (M, bucket) step-valid mask."""
        with jax.profiler.TraceAnnotation("plane.stage_fleet",
                                          stage_seed=seed, clients=self.M):
            staged = []
            nbs = []
            for c in self.fleet:
                k = local_steps_override or c.local_steps
                b = self._staged_batches(c.cid, k, seed)
                staged.append(b)
                nbs.append(_num_batches(b))
            bucket = self._bucketed(max(nbs))
            batches = jax.tree.map(
                lambda *xs: np.stack(xs),
                *[_pad_batches(b, bucket) for b in staged])
            valid = np.arange(bucket)[None, :] < np.asarray(nbs)[:, None]
        return batches, valid

    def train_all(self, g_flat: jnp.ndarray, seed: int,
                  local_steps_override: Optional[int] = None) -> jnp.ndarray:
        """One fleet-wide round (FedAvg round / baseline-AFL broadcast):
        vmap the scanned local SGD across all M rows — ONE launch, into
        a fresh (M, n) buffer."""
        batches, valid = self._stage_fleet(seed, local_steps_override)
        return self._fleet_rows(g_flat, batches, valid)

    def _fleet_rows(self, g_flat, batches, valid) -> jnp.ndarray:
        """Every staged client's local SGD from ``g_flat``: (C, n) rows."""
        return self._train_all(self._new_rows(valid.shape[0]), g_flat,
                               batches, valid, self._lstep)

    def train_all_runs(self, g_flats: jnp.ndarray, batches,
                       valid) -> jnp.ndarray:
        """R runs' fleet-wide rounds as ONE launch: ``g_flats`` is (R, n),
        ``batches``/``valid`` are the R runs' ``_stage_fleet`` outputs
        stacked on a new leading run axis.  Returns the (R, M, n) stacked
        fleet buffers.  Used by the sweep plane for batched fleet init and
        the §III-B baseline's every-M broadcast (docs/DESIGN.md §8)."""
        return self._train_all_runs(g_flats, batches, valid, self._lstep)

    def train_row(self, fleet_buf: jnp.ndarray, g_flat: jnp.ndarray,
                  cid: int, num_steps: int, seed: int) -> jnp.ndarray:
        """Client ``cid`` trains from the fresh global (eq. 4): scan over
        its staged batches, row written back via dynamic_update_slice —
        ONE launch per upload event."""
        batches, valid = self._stage_one(cid, num_steps, seed)
        return self._train_row(fleet_buf, g_flat, jnp.int32(cid),
                               batches, valid, self._lstep)

    def local_train_flat(self, flat: jnp.ndarray, cid: int, num_steps: int,
                         seed: int) -> jnp.ndarray:
        """Standalone row training (no fleet buffer) — the threaded async
        runtime's client workers hold their own flat model."""
        batches, valid = self._stage_one(cid, num_steps, seed)
        return self._train_flat(flat, batches, valid, self._lstep)

    def train_rows(self, fleet_buf: jnp.ndarray,
                   entries: Sequence) -> jnp.ndarray:
        """Event-window batched retrain: ``entries`` is a list of
        ``(cid, g_flat, num_steps, seed)`` for a window of upload events
        with DISTINCT cids.  Each client trains from the global it
        received at its own event (the exact per-event snapshots), but
        the W retrains run as ONE vmapped launch — valid because a
        client's retrain is only consumed at its NEXT upload, which is
        outside the window by construction.  Same math as W sequential
        ``train_row`` calls; W and the batch counts are both pow2-
        bucketed (pads duplicate entry 0, writing row cids[0] twice with
        the identical value)."""
        cids = [e[0] for e in entries]
        if len(set(cids)) != len(cids):
            raise ValueError("event-window entries must have distinct cids")
        staged = [self._staged_batches(cid, k, seed)
                  for cid, _, k, seed in entries]
        nbs = [_num_batches(b) for b in staged]
        nb_bucket = self._bucketed(max(nbs))
        W = len(entries)
        w_bucket = pow2_bucket(W) if self.bucket else W
        pad = w_bucket - W
        batches = [_pad_batches(b, nb_bucket) for b in staged]
        batches += [batches[0]] * pad
        batches = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        valid = np.arange(nb_bucket)[None, :] < \
            np.asarray(nbs + nbs[:1] * pad)[:, None]
        cids_arr = jnp.asarray(cids + cids[:1] * pad, jnp.int32)
        gs = jnp.stack([e[1] for e in entries]
                       + [entries[0][1]] * pad)
        return self._train_rows(fleet_buf, gs, cids_arr, batches, valid,
                                self._lstep)

    # -- conveniences ---------------------------------------------------------
    def flatten(self, tree) -> jnp.ndarray:
        return self.engine.flatten(tree)

    def unflatten(self, flat: jnp.ndarray):
        return self.engine.unflatten(flat)


class ShardedClientPlane(ClientPlane):
    """Fleet plane sharded over a ``("fleet",)`` device mesh (DESIGN.md §6).

    The (M, n) client-state matrix is block-partitioned by row over the
    mesh's ``fleet`` axis (client ``cid`` -> shard ``cid // rows_per_shard``,
    padded up to ``M_pad`` so every shard holds an equal block); the global
    flat model stays replicated.  All fleet-touching programs run inside
    ``shard_map``:

    * ``train_all`` vmaps the scanned local SGD over each shard's OWN row
      block (per-shard batch stacks arrive pre-partitioned on the leading
      axis) — fleet-wide rounds scale with M/D;
    * ``train_rows`` batches an event window's retrains PER SHARD: the
      window is grouped by owning shard on the host, each shard's list is
      padded to the bucketed per-shard maximum (pads duplicate the
      shard's first entry, or no-op-rewrite row 0 on shards with no
      events, so duplicate scatters always carry identical values), and
      one launch retrains every shard's slice concurrently;
    * ``train_row`` runs the single-event scan on every shard (SPMD) and
      masks the row write to the owner;
    * the blends go through :class:`~repro.core.agg_engine.ShardedRowEngine`
      (``self.engine``), which resolves global row indices to
      (shard, local-row) inside the program and psum-gathers ONLY the
      addressed row — the fleet buffer itself is never gathered.

    ``mesh`` defaults to ``repro.launch.mesh.make_fleet_mesh()`` (every
    host device).  With one device this degrades exactly to the base
    plane's math (parity-tested), so the same code path serves laptop and
    pod.
    """

    def __init__(self, engine: AggEngine, fleet: Sequence[ClientSpec],
                 step_fn: StepFn, batch_fn: BatchFn, *, mesh=None,
                 window_cap: Optional[int] = None, **plane_kw):
        super().__init__(engine, fleet, step_fn, batch_fn, **plane_kw)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.core.agg_engine import ShardedRowEngine
        from repro.launch.mesh import make_fleet_mesh
        from repro.sharding import specs as sspec

        self.mesh = make_fleet_mesh() if mesh is None else mesh
        # the step's bound arrays, replicated over the mesh once (an
        # unplaced argument would be copied to every device per launch)
        self._lstep = jax.device_put(self._lstep,
                                     NamedSharding(self.mesh, P()))
        D = self.mesh.shape[sspec.FLEET_AXIS]
        self.layout = sspec.FleetLayout(self.M, D)
        # self.engine becomes the shard-aware wrapper; runtimes address
        # rows through it without knowing the buffer is distributed
        self.engine = ShardedRowEngine(engine, self.mesh, self.layout)
        self._ax = sspec.FLEET_AXIS
        self._P = P
        self._sspec = sspec
        self._prog_cache = {}
        self.window_cap = window_cap

    # -- shard_map program builders (cached per batch-tree structure) -------
    def _program(self, name, treedef, builder):
        key = (name, treedef)
        prog = self._prog_cache.get(key)
        if prog is None:
            prog = builder()
            self._prog_cache[key] = prog
        return prog

    def compiled_variants(self) -> int:
        """Total TRACED program variants across the plane's jitted
        shard_map programs (one per distinct bucketed shape), the honest
        'no recompile-per-event' signal — the _prog_cache key count only
        reflects batch-tree structures, not per-shape retraces."""
        total = 0
        for prog in self._prog_cache.values():
            size = getattr(prog, "_cache_size", None)
            total += int(size()) if callable(size) else 1
        return total

    def _sharded_train_all(self, batches):
        P, ax = self._P, self._ax
        fleet_train, new_rows = self._fleet_train, self._new_rows

        def body(g, b, v, lstep):
            return fleet_train(new_rows(v.shape[0]), g, b, v, lstep)

        specs = (P(), self._sspec.fleet_batch_specs(batches),
                 P(ax, None), P())
        f = jax.shard_map(body, mesh=self.mesh, in_specs=specs,
                          out_specs=self._sspec.fleet_buffer_spec(),
                          check_vma=False)
        return jax.jit(f)

    def _sharded_train_row(self, batches):
        P, ax, scan_train = self._P, self._ax, self._scan_train
        m_loc = self.layout.rows_per_shard

        def body(buf, g, cid, b, v, lstep):
            new = scan_train(g, b, v, lstep)   # every shard computes (SPMD)
            shard = cid // m_loc
            lrow = cid - shard * m_loc
            cur = jax.lax.dynamic_slice_in_dim(buf, lrow, 1, axis=0)
            row = jnp.where(jax.lax.axis_index(ax) == shard,
                            new[None].astype(buf.dtype), cur)
            return jax.lax.dynamic_update_slice_in_dim(buf, row, lrow,
                                                       axis=0)

        specs = (self._sspec.fleet_buffer_spec(), P(), P(),
                 jax.tree.map(lambda _: P(), batches), P(), P())
        f = jax.shard_map(body, mesh=self.mesh, in_specs=specs,
                          out_specs=self._sspec.fleet_buffer_spec(),
                          check_vma=False)
        return jax.jit(f, donate_argnums=(0,) if self.donate else ())

    def _sharded_train_rows(self, batches):
        P, ax = self._P, self._ax
        fleet_train, new_rows = self._fleet_train, self._new_rows

        def body(buf, gs, lcids, wvalid, b, v, lstep):
            rows = fleet_train(new_rows(v.shape[0]), gs, b, v, lstep,
                               g_axis=0)                   # (W_b, n)
            cur = buf[lcids]
            out = jnp.where(wvalid[:, None], rows.astype(buf.dtype), cur)
            # duplicate lcids (pads) always scatter identical values, so
            # the undefined duplicate-write order cannot corrupt a row
            return buf.at[lcids].set(out)

        specs = (self._sspec.fleet_buffer_spec(), P(ax, None), P(ax),
                 P(ax), self._sspec.fleet_batch_specs(batches), P(ax, None),
                 P())
        f = jax.shard_map(body, mesh=self.mesh, in_specs=specs,
                          out_specs=self._sspec.fleet_buffer_spec(),
                          check_vma=False)
        return jax.jit(f, donate_argnums=(0,) if self.donate else ())

    # -- fused local training (sharded) -------------------------------------
    def train_all(self, g_flat: jnp.ndarray, seed: int,
                  local_steps_override: Optional[int] = None) -> jnp.ndarray:
        """One fleet-wide round, each shard training its own M/D rows
        concurrently.  Rows padded up to M_pad carry an all-False step
        mask (they come back as copies of the global) and zero
        coefficients in every blend."""
        batches, valid = self._stage_fleet(seed, local_steps_override)
        pad = self.layout.M_pad - self.M
        if pad:
            batches = jax.tree.map(
                lambda x: np.concatenate(
                    [x, np.repeat(x[:1], pad, axis=0)]), batches)
            valid = np.concatenate(
                [valid, np.zeros((pad,) + valid.shape[1:], bool)])
        prog = self._program("train_all", jax.tree.structure(batches),
                             lambda: self._sharded_train_all(batches))
        return prog(g_flat, batches, valid, self._lstep)

    def train_row(self, fleet_buf: jnp.ndarray, g_flat: jnp.ndarray,
                  cid: int, num_steps: int, seed: int) -> jnp.ndarray:
        batches, valid = self._stage_one(cid, num_steps, seed)
        prog = self._program("train_row", jax.tree.structure(batches),
                             lambda: self._sharded_train_row(batches))
        return prog(fleet_buf, g_flat, jnp.int32(cid), batches, valid,
                    self._lstep)

    def train_rows(self, fleet_buf: jnp.ndarray,
                   entries: Sequence) -> jnp.ndarray:
        """Event-window batched retrain, grouped by owning shard: one
        launch trains every shard's slice of the window concurrently.
        Same contract as the base plane (distinct cids; per-event global
        snapshots), same math to ≤1e-5."""
        cids = [e[0] for e in entries]
        if len(set(cids)) != len(cids):
            raise ValueError("event-window entries must have distinct cids")
        D = self.layout.D
        per_shard: list = [[] for _ in range(D)]
        for e in entries:
            per_shard[self.layout.shard_of(e[0])].append(e)
        staged = {e[0]: self._staged_batches(e[0], e[2], e[3])
                  for e in entries}
        nbs = {cid: _num_batches(b) for cid, b in staged.items()}
        nb_bucket = self._bucketed(max(nbs.values()))
        W = max(len(p) for p in per_shard)
        w_bucket = pow2_bucket(W) if self.bucket else W

        gs, lcids, wvalid, batch_list, svalid = [], [], [], [], []
        for s in range(D):
            es = per_shard[s]
            # pads duplicate the shard's first entry (identical trained
            # row -> identical duplicate writes); an event-less shard
            # no-op-rewrites its row 0 (wvalid False -> writes back the
            # current value, again identical across duplicates)
            slots = (es + es[:1] * (w_bucket - len(es))) if es \
                else [entries[0]] * w_bucket
            for k, (cid, g_snap, _steps, _seed) in enumerate(slots):
                live = bool(es)
                lcids.append(self.layout.local_row(cid) if live else 0)
                wvalid.append(live)
                gs.append(g_snap)
                b = staged[cid]
                batch_list.append(_pad_batches(b, nb_bucket))
                nb = nbs[cid]
                svalid.append((np.arange(nb_bucket) < nb) if live
                              else np.zeros(nb_bucket, bool))
        batches = jax.tree.map(lambda *xs: np.stack(xs), *batch_list)
        prog = self._program("train_rows", jax.tree.structure(batches),
                             lambda: self._sharded_train_rows(batches))
        return prog(fleet_buf, jnp.stack(gs),
                    np.asarray(lcids, np.int32), np.asarray(wvalid),
                    batches, np.stack(svalid), self._lstep)


class PagedClientPlane(ClientPlane):
    """Active-set client plane: (P, n) device slots over an (M, n) host
    arena (docs/DESIGN.md §12).

    The fleet buffer this plane hands the runtimes is the SLOT POOL —
    a (P, n) device array with P ≪ M — backed by a
    :class:`~repro.core.fleet_store.FleetStore` arena holding every cold
    row on the host.  All of the base plane's fused expressions run
    unchanged against the pool; only the addressing changes:

    * blends go through :class:`~repro.core.agg_engine.PagedRowEngine`
      (``self.engine``), which resolves cid → slot host-side;
    * ``train_rows`` stages batches by TRUE cid but scatters trained
      rows by slot (``ensure_resident`` first, so every uploader in the
      window is pool-resident);
    * ``init_fleet`` is LAZY: it records the (w_0, seed) recipe and
      returns a zero pool — a client's row is materialized (trained from
      the recorded broadcast) the first time it becomes resident.  Rows
      the schedule never touches are never trained NOR device-resident,
      which is what lets an M=100k run fit a P=64 pool.  Materialized
      rows are bit-identical to the dense ``init_fleet`` rows: the
      per-client batch draws are the same calls, and pow2 step padding
      is value-neutral under the scan's valid-mask.
    * fleet-wide rounds (``train_all`` — the §III-B broadcast and FedAvg
      rounds) stream the whole fleet through the device P rows at a
      time, writing results to the arena, then hand back a fresh pool
      (the old pool's rows are all superseded).

    ``active_slots`` defaults to min(M, 64); ``prefetch_depth`` bounds
    the exact-prefetch pipeline (``FleetStore.plan``/``adopt``) the
    compiled-loop runner drives.
    """

    paged = True

    def __init__(self, engine: AggEngine, fleet: Sequence[ClientSpec],
                 step_fn: StepFn, batch_fn: BatchFn, *,
                 active_slots: Optional[int] = None,
                 prefetch_depth: int = 2, **plane_kw):
        super().__init__(engine, fleet, step_fn, batch_fn, **plane_kw)
        from repro.core.agg_engine import PagedRowEngine
        from repro.core.fleet_store import FleetStore

        P = int(active_slots) if active_slots else min(self.M, 64)
        self.store = FleetStore(self.M, engine.n, P, engine.storage_dtype,
                                prefetch_depth=prefetch_depth)
        self.P = self.store.P
        self.engine = PagedRowEngine(engine, self)
        self._base_engine = engine
        self._init_recipe = None            # (w0 numpy, seed) for lazy rows

    # -- addressing ----------------------------------------------------------
    def slot_index(self, cid: int) -> int:
        s = int(self.store.slot_map[cid])
        if s < 0:
            raise KeyError(
                f"client {cid} is not pool-resident — ensure_resident() "
                "must run before any row-addressed blend")
        return s

    def ensure_resident(self, pool, cids):
        """Materialize-then-page: lazy-init any first-touch rows into the
        arena, then make every requested cid slot-resident."""
        cids = np.unique(np.asarray(cids, np.int64))
        self._materialize(cids)
        return self.store.ensure(pool, cids)

    def adopt_chunk(self, pool, cids):
        """Prefetch-aware twin of ``ensure_resident``: consume the next
        staged chunk from the store's plan (compiled-loop path)."""
        cids = np.unique(np.asarray(cids, np.int64))
        self._materialize(cids)
        return self.store.adopt(pool, cids)

    def warm_trace(self, cids) -> None:
        """Materialize every uploader the trace will touch BEFORE the
        prefetch plan starts staging, so staged copies are never of
        uninitialized rows (they would be version-rejected anyway, but
        warm staging makes the prefetch exact instead of wasted)."""
        self._materialize(np.unique(np.asarray(cids, np.int64)))

    def memory_stats(self) -> dict:
        return self.store.memory_stats()

    # -- lazy materialization ------------------------------------------------
    def _materialize(self, cids: np.ndarray) -> None:
        todo = cids[~self.store.initialized[cids]]
        if todo.size == 0:
            return
        if self._init_recipe is None:
            raise RuntimeError(
                "paged plane has no init recipe — call init_fleet() (or "
                "load_store_state() on resume) before touching rows")
        g0, seed = self._init_recipe
        g0 = jnp.asarray(g0)
        for a in range(0, todo.size, self.P):
            chunk = todo[a:a + self.P]
            rows = self._train_chunk(g0, chunk, seed, None)
            self.store.write_rows(chunk, rows)
            self.store.note_transient(chunk.size)

    def _train_chunk(self, g_dev, cids: np.ndarray, seed: int,
                     local_steps_override: Optional[int]) -> np.ndarray:
        """Train |chunk| rows from one shared global — the streaming unit
        of lazy init and fleet-wide rounds.  Chunk width pow2-pads by
        repeating entry 0 (bounds program variants to log2(P))."""
        staged, nbs = [], []
        for cid in cids:
            k = local_steps_override or self.fleet[int(cid)].local_steps
            b = self._staged_batches(int(cid), k, seed)
            staged.append(b)
            nbs.append(_num_batches(b))
        bucket = self._bucketed(max(nbs))
        k = len(staged)
        kb = pow2_bucket(k) if self.bucket else k
        trees = [_pad_batches(b, bucket) for b in staged]
        trees += trees[:1] * (kb - k)
        batches = jax.tree.map(lambda *xs: np.stack(xs), *trees)
        valid = np.arange(bucket)[None, :] < \
            np.asarray(nbs + nbs[:1] * (kb - k))[:, None]
        rows = self._fleet_rows(g_dev, batches, valid)
        return np.asarray(rows[:k])

    # -- fused local training (slot-addressed) -------------------------------
    def init_fleet(self, g_flat: jnp.ndarray, seed: int) -> jnp.ndarray:
        """Record the lazy-init recipe and hand back an empty pool."""
        self._init_recipe = (np.asarray(g_flat), int(seed))
        self.store.initialized[:] = False
        self.store.row_version += 1
        self.store.cancel_plan()
        self.store.reset_slots()
        return jnp.zeros((self.P, self.engine.n),
                         self._base_engine.storage_dtype)

    def train_all(self, g_flat: jnp.ndarray, seed: int,
                  local_steps_override: Optional[int] = None) -> jnp.ndarray:
        """Fleet-wide round, streamed P rows at a time through the
        device into the arena.  Returns a FRESH empty pool: every old
        pool row is superseded by the round, so residency restarts."""
        for a in range(0, self.M, self.P):
            chunk = np.arange(a, min(a + self.P, self.M))
            rows = self._train_chunk(g_flat, chunk, seed,
                                     local_steps_override)
            self.store.write_rows(chunk, rows)
            self.store.note_transient(chunk.size)
        self.store.cancel_plan()
        self.store.reset_slots()
        return jnp.zeros((self.P, self.engine.n),
                         self._base_engine.storage_dtype)

    def seed_store_from_staged(self, g_flat, staged_fleet) -> None:
        """Arena-resident fleet round from a pre-staged ``_stage_fleet``
        batch stack (the sweep plane's init/broadcast path — the staging
        and its fleet-wide bucket are shared with the dense twin, and
        both run the plane's one fleet-round expression, so the rows are
        ``train_all_runs``'s up to the compiler's order of sums)."""
        batches, valid = staged_fleet
        for a in range(0, self.M, self.P):
            hi = min(a + self.P, self.M)
            b = jax.tree.map(lambda x: x[a:hi], batches)
            v = valid[a:hi]
            k = hi - a
            kb = pow2_bucket(k) if self.bucket else k
            if kb > k:
                b = jax.tree.map(
                    lambda x: np.concatenate(
                        [x, np.repeat(x[:1], kb - k, axis=0)]), b)
                v = np.concatenate([v, np.repeat(v[:1], kb - k, axis=0)])
            rows = self._fleet_rows(g_flat, b, v)
            self.store.write_rows(np.arange(a, hi), np.asarray(rows)[:k])
            self.store.note_transient(k)
        self.store.cancel_plan()
        self.store.reset_slots()

    def train_row(self, fleet_buf: jnp.ndarray, g_flat: jnp.ndarray,
                  cid: int, num_steps: int, seed: int) -> jnp.ndarray:
        fleet_buf = self.ensure_resident(fleet_buf, [cid])
        batches, valid = self._stage_one(cid, num_steps, seed)
        self.store.mark_dirty(np.asarray([cid]))
        return self._train_row(fleet_buf, g_flat,
                               jnp.int32(self.slot_index(cid)),
                               batches, valid, self._lstep)

    def train_rows(self, fleet_buf: jnp.ndarray,
                   entries: Sequence) -> jnp.ndarray:
        """Event-window batched retrain against the slot pool: batches
        stage by TRUE cid, trained rows scatter by slot.  Windows wider
        than P split into P-sized chunks (each chunk ensures residency
        before its launch)."""
        cids = [e[0] for e in entries]
        if len(set(cids)) != len(cids):
            raise ValueError("event-window entries must have distinct cids")
        for a in range(0, len(entries), self.P):
            chunk = entries[a:a + self.P]
            ccids = np.asarray([e[0] for e in chunk], np.int64)
            fleet_buf = self.ensure_resident(fleet_buf, ccids)
            fleet_buf = self._train_rows_paged(fleet_buf, chunk)
            self.store.mark_dirty(ccids)
        return fleet_buf

    def _train_rows_paged(self, pool, entries: Sequence) -> jnp.ndarray:
        staged = [self._staged_batches(cid, k, seed)
                  for cid, _, k, seed in entries]
        nbs = [_num_batches(b) for b in staged]
        nb_bucket = self._bucketed(max(nbs))
        W = len(entries)
        w_bucket = pow2_bucket(W) if self.bucket else W
        pad = w_bucket - W
        batches = [_pad_batches(b, nb_bucket) for b in staged]
        batches += [batches[0]] * pad
        batches = jax.tree.map(lambda *xs: np.stack(xs), *batches)
        valid = np.arange(nb_bucket)[None, :] < \
            np.asarray(nbs + nbs[:1] * pad)[:, None]
        slots = [self.slot_index(e[0]) for e in entries]
        slots_arr = jnp.asarray(slots + slots[:1] * pad, jnp.int32)
        gs = jnp.stack([e[1] for e in entries]
                       + [entries[0][1]] * pad)
        return self._train_rows(pool, gs, slots_arr, batches, valid,
                                self._lstep)

    # -- fleet-wide weighted sum (the FedAvg-cycle consumer) -----------------
    def fleet_weighted_sum(self, coef0, g_flat, coefs, pool) -> jnp.ndarray:
        """w ← c0·w + Σ c_m·arena[m] as a chunked f32 accumulation —
        the pool flushes first so dirty rows contribute their current
        values.  Matches the dense single tensordot ≤1e-5 (partial-sum
        reordering only)."""
        self.store.flush(pool)
        coefs = np.asarray(coefs, np.float32)
        if coefs.shape[0] != self.M:
            raise ValueError(
                f"fleet weighted sum needs one coefficient per client "
                f"({self.M}), got {coefs.shape[0]}")
        if "_fws_acc" not in self.__dict__:
            def acc_fn(acc, rows, cf):
                return acc + jnp.tensordot(cf, rows.astype(jnp.float32),
                                           axes=(0, 0))
            self._fws_acc = jax.jit(acc_fn)
        acc = jnp.float32(coef0) * g_flat.astype(jnp.float32)
        C = self.P
        for a in range(0, self.M, C):
            hi = min(a + C, self.M)
            rows = self.store.arena[a:hi]
            cf = coefs[a:hi]
            if hi - a < C:                     # fixed chunk shape
                padn = C - (hi - a)
                rows = np.concatenate(
                    [rows, np.zeros((padn, self.store.n),
                                    self.store.dtype)])
                cf = np.concatenate([cf, np.zeros(padn, np.float32)])
            self.store.note_transient(C)
            acc = self._fws_acc(acc, rows, cf)
        return acc.astype(self._base_engine.storage_dtype)

    # -- checkpoint round-trip ----------------------------------------------
    def store_state(self, pool) -> dict:
        """Spill the store (flushed arena + slot table + counters + the
        lazy-init recipe) for ``ckpt.save_afl_state``'s ``fleet_store``
        extra."""
        st = self.store.state_dict(pool)
        g0, seed = self._init_recipe if self._init_recipe is not None \
            else (np.zeros(self.store.n, self.store.dtype), 0)
        st["init_g"] = np.asarray(g0)
        st["init_seed"] = np.asarray(seed, np.int64)
        return st

    def load_store_state(self, state: dict) -> None:
        self.store.load_state(state)
        self._init_recipe = (np.asarray(state["init_g"]),
                             int(np.asarray(state["init_seed"])))


def build_plane(engine: AggEngine, fleet: Sequence[ClientSpec],
                step_fn: StepFn, batch_fn: BatchFn, *,
                sharded: bool = False, store: str = "dense",
                active_slots: Optional[int] = None,
                prefetch_depth: int = 2,
                window_cap: Optional[int] = None, **plane_kw):
    """Single constructor for every plane flavor — the resolution point
    tasks route ``PlaneConfig`` through (``store`` / ``active_slots`` /
    ``prefetch_depth`` arrive from ``RunConfig.plane``; ``sharded`` from
    ``plane.kind``)."""
    if store not in ("dense", "paged"):
        raise ValueError(f"plane store must be dense|paged, got '{store}'")
    if store == "paged":
        if sharded:
            raise ValueError(
                "paged store and sharded plane are mutually exclusive — "
                "a paged pool is single-device by construction")
        plane = PagedClientPlane(engine, fleet, step_fn, batch_fn,
                                 active_slots=active_slots,
                                 prefetch_depth=prefetch_depth, **plane_kw)
        plane.window_cap = window_cap
        return plane
    if sharded:
        return ShardedClientPlane(engine, fleet, step_fn, batch_fn,
                                  window_cap=window_cap, **plane_kw)
    plane = ClientPlane(engine, fleet, step_fn, batch_fn, **plane_kw)
    plane.window_cap = window_cap
    return plane
