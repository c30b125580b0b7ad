"""Fused flat-buffer aggregation engine — the server-blend data plane.

Every server-side blend in the repo (the paper's eq. 3 / eq. 11 and their
folded-trunk and FedAvg-cycle forms) routes through this module instead of
per-leaf ``jax.tree.map`` chains.  See docs/DESIGN.md §3 for the full
design; the short version:

* the model pytree is flattened ONCE into a contiguous (n,) buffer
  (ravel/unravel plans are cached per tree-structure, so repeated engines
  over the same architecture share nothing but cheap metadata);
* every blend variant is ONE jitted program: flatten(client) → fused
  multiply-accumulate over the flat buffer → unflatten — a single
  dispatch and a single HBM round-trip per stream, instead of O(leaves)
  dispatches with 2 round-trips per leaf per event;
* on TPU the MAC is the Pallas ``weighted_agg_flat2d`` launch in native
  (8, 128) tiles (``mode="kernel"``); off-TPU it lowers to the jnp oracle
  (``mode="xla"``) — same math, XLA-fused, because the Pallas interpreter
  pays a full-buffer copy per launch and would bury the fusion win.
  ``interpret=True`` forces the kernel path through the interpreter
  (parity tests do this so the real kernel runs in tier-1 on CPU);
* the global flat buffer is donated across steps (TPU/GPU), so the blend
  is in-place at the XLA level;
* storage follows the model dtype (bf16 storage + f32 accumulation in the
  mixed-precision setup); coefficients are always f32.

Blend variants:

* ``blend``         — single-event eq. (3): C=1 fast-path kernel.
* ``blend_trunk``   — K queued arrivals folded with
  ``aggregation.fold_sequential_blends`` into ONE C=K kernel launch.
* ``weighted_sum``  — the baseline per-cycle FedAvg reproduction
  (eq. 2/7): w ← c0·w + Σ α_m·w_m as one C=M launch.

Row-addressed variants (the client-plane data path, docs/DESIGN.md §4):
when the fleet's models live as one device-resident (M, n) stacked flat
buffer (``core.client_plane``), the uploading client's weights are a ROW
of that buffer — no pytree exists to flatten.  ``blend_row_flat`` /
``delta_row_flat`` ``dynamic_slice`` the row inside the jitted program,
``blend_rows_flat`` / ``weighted_sum_rows_flat`` feed already-stacked
(C, n) rows straight into the MAC.  These eliminate the per-event
per-leaf ``jnp.concatenate`` re-flatten entirely.

``delta_flat`` / ``delta_row_flat`` produce the FedOpt pseudo-gradient
(1-β)(w − w_m) as one fused f32 launch — the server-optimizer path then
runs entirely on the flat buffer (a flat array is a valid single-leaf
pytree for ``repro.optim.optimizers``).

``weighted_sum_leaves`` is the per-leaf twin used where leaves must stay
individually sharded (the GSPMD fused step in ``core/distributed.py``) —
there the flat concatenate would fight the partitioner, so the engine
only centralizes the math, not the layout.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.kernels import pallas_interpret
from repro.kernels.weighted_agg.weighted_agg import (weighted_agg_flat2d,
                                                     weighted_agg_runs_flat2d)


def _can_donate() -> bool:
    return jax.default_backend() in ("tpu", "gpu")


def pow2_bucket(n: int) -> int:
    """Next power of two ≥ n — the shared bucketing policy for trunk
    widths, event-window widths and scan lengths (bounds compile variants
    to log2 instead of one per distinct size)."""
    if n <= 0:
        raise ValueError("bucket size must be positive")
    return 1 << (n - 1).bit_length()


class AggEngine:
    """Flat-buffer blend engine for one model tree-structure.

    ``template`` is any pytree of arrays (or ShapeDtypeStructs) with the
    target structure; the engine records shapes/dtypes/offsets and builds
    jitted flatten / unflatten / blend programs around them.

    ``mode`` picks the MAC backend: "kernel" (Pallas launch — the default
    on TPU, or anywhere when ``interpret=True`` is passed) or "xla" (jnp
    oracle — the default off-TPU).  Both are the same math to float
    rounding; parity tests pin them against each other.
    """

    def __init__(self, template, *, block_rows: Optional[int] = None,
                 interpret: Optional[bool] = None, mode: Optional[str] = None,
                 storage_dtype=None, donate: Optional[bool] = None):
        leaves, treedef = jax.tree.flatten(template)
        if not leaves:
            raise ValueError("template pytree has no leaves")
        self.treedef = treedef
        self.shapes = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
        self.sizes = tuple(int(np.prod(s)) if s else 1 for s in self.shapes)
        self.offsets = tuple(np.cumsum((0,) + self.sizes[:-1]).tolist())
        self.n = int(sum(self.sizes))
        if mode is None:
            # explicit interpret request means "run the real kernel"
            mode = "kernel" if (interpret or not pallas_interpret()) \
                else "xla"
        if mode not in ("kernel", "xla"):
            raise ValueError(f"unknown engine mode '{mode}'")
        self.mode = mode
        self.interpret = (pallas_interpret() if interpret is None
                          else interpret)
        # one whole-buffer grid step under the interpreter (it pays a
        # full-buffer copy per step); VMEM-sized blocks on real TPUs
        self.block_rows = (block_rows if block_rows is not None
                           else (None if self.interpret else 512))
        self.storage_dtype = jnp.dtype(
            storage_dtype if storage_dtype is not None
            else jnp.result_type(*self.dtypes))
        donate = _can_donate() if donate is None else donate
        self.donate = donate
        kern = functools.partial(weighted_agg_flat2d,
                                 block_rows=self.block_rows,
                                 interpret=self.interpret)
        runs_kern = functools.partial(weighted_agg_runs_flat2d,
                                      block_rows=self.block_rows,
                                      interpret=self.interpret)
        # the raw (n,) x (C, n) MAC launch, for the shard_map programs
        # that run it per shard (ShardedRowEngine, the sharded scan)
        self.kern = kern

        def flatten_expr(tree):
            """A tree with the template's structure -> one (n,) row in
            the storage dtype."""
            ls = treedef.flatten_up_to(tree)
            return jnp.concatenate(
                [jnp.ravel(x).astype(self.storage_dtype) for x in ls])

        def flatten_rows_expr(out, trees):
            """Trees stacked on a leading axis of C -> written leaf by
            leaf into the (C, n) rows ``out``, in its dtype (one in-place
            update per leaf where ``out`` is donated)."""
            c = out.shape[0]
            for x, off, sz in zip(treedef.flatten_up_to(trees),
                                  self.offsets, self.sizes):
                out = jax.lax.dynamic_update_slice_in_dim(
                    out, x.reshape(c, sz).astype(out.dtype), off, axis=1)
            return out

        def split_expr(flat):
            """(n,) row -> the template's tree of reshaped slices, still
            in the ROW's dtype (the client plane's scan carry, §4)."""
            return jax.tree.unflatten(treedef, [
                flat[off:off + sz].reshape(sh)
                for off, sz, sh in zip(self.offsets, self.sizes,
                                       self.shapes)])

        def cast_expr(tree):
            """Each leaf to the template's dtype (a no-op where it is)."""
            return jax.tree.unflatten(treedef, [
                x.astype(dt)
                for x, dt in zip(treedef.flatten_up_to(tree), self.dtypes)])

        def unflatten_expr(flat):
            return cast_expr(split_expr(flat))

        def mac_xla(g_flat, client_trees, coefs):
            """Oracle MAC: stack-free FMA chain XLA fuses into one pass
            (the flatten concats feed the elementwise consumers, so no
            (C, n) intermediate is ever materialized)."""
            acc = coefs[0] * g_flat.astype(jnp.float32)
            for i, t in enumerate(client_trees):
                acc = acc + coefs[i + 1] * \
                    flatten_expr(t).astype(jnp.float32)
            return acc.astype(self.storage_dtype)

        def blend_one(g_flat, client_tree, coefs):
            if self.mode == "kernel":
                w = flatten_expr(client_tree)[None]        # (1, n)
                new = kern(g_flat, w, coefs)
            else:
                new = mac_xla(g_flat, (client_tree,), coefs)
            return new, unflatten_expr(new)

        def blend_many(g_flat, client_trees, coefs):
            if self.mode == "kernel":
                w = jnp.stack([flatten_expr(t)
                               for t in client_trees])     # (C, n)
                new = kern(g_flat, w, coefs)
            else:
                new = mac_xla(g_flat, client_trees, coefs)
            return new, unflatten_expr(new)

        def mac_rows(g_flat, rows, coefs):
            """Rows are ALREADY flat (C, n) — no flatten, pure MAC."""
            if self.mode == "kernel":
                return kern(g_flat, rows, coefs)
            acc = coefs[0] * g_flat.astype(jnp.float32)
            acc = acc + jnp.tensordot(coefs[1:], rows.astype(jnp.float32),
                                      axes=(0, 0))
            return acc.astype(self.storage_dtype)

        def blend_row_expr(g_flat, row, coefs):
            """TRACEABLE single-row eq. (3): ``row`` is the already-sliced
            (n,) client row.  This is the donation-safe form — it owns no
            jit boundary, so when inlined into a larger donated program
            (the §7 event-trace scan carries (fleet_buf, g_flat) through
            ``lax.scan`` with ``donate_argnums``) the blend reuses the
            caller's buffers instead of allocating per event."""
            if self.mode == "kernel":
                return kern(g_flat, row[None], coefs)
            acc = (coefs[0] * g_flat.astype(jnp.float32)
                   + coefs[1] * row.astype(jnp.float32))
            return acc.astype(self.storage_dtype)

        def delta_row_expr(g_flat, row, scale):
            """Traceable FedOpt pseudo-gradient scale·(w − row), (n,) f32."""
            return scale * (g_flat.astype(jnp.float32)
                            - row.astype(jnp.float32))

        def blend_runs_expr(g_flats, rows, coefs):
            """RUN-BATCHED traceable eq. (3): R independent runs' globals
            blend against R uploaded rows in one expression — ``g_flats``
            and ``rows`` are (R, n), ``coefs`` is (R, 2).  Per-run math is
            elementwise-identical to :func:`blend_row_expr` (the sweep
            plane's run-parity bound relies on this).  Kernel mode is one
            Pallas launch with the run axis on its grid; XLA mode is one
            broadcasted FMA."""
            if self.mode == "kernel":
                return runs_kern(g_flats, rows[:, None], coefs)
            acc = (coefs[:, :1] * g_flats.astype(jnp.float32)
                   + coefs[:, 1:] * rows.astype(jnp.float32))
            return acc.astype(self.storage_dtype)

        def delta_runs_expr(g_flats, rows, scales):
            """Run-batched FedOpt pseudo-gradients: (R, n) f32 from (R, n)
            carries and (R,) scales."""
            return scales[:, None] * (g_flats.astype(jnp.float32)
                                      - rows.astype(jnp.float32))

        def blend_row(g_flat, fleet_buf, cid, coefs):
            """eq. (3) against row ``cid`` of the (M, n) fleet buffer."""
            row = jax.lax.dynamic_slice_in_dim(fleet_buf, cid, 1, axis=0)
            return blend_row_expr(g_flat, row[0], coefs)

        def mac_cids(g_flat, fleet_buf, cids, coefs):
            """Folded trunk whose C client models are rows of the fleet
            buffer, gathered INSIDE the program — one launch for an
            ingest micro-batch, no (C, n) host-side staging copy."""
            rows = jnp.take(fleet_buf, cids, axis=0)
            return mac_rows(g_flat, rows, coefs)

        def delta_row(g_flat, fleet_buf, cid, scale):
            row = jax.lax.dynamic_slice_in_dim(fleet_buf, cid, 1, axis=0)[0]
            return delta_row_expr(g_flat, row, scale)

        def delta_one(g_flat, client_tree, scale):
            return scale * (g_flat.astype(jnp.float32)
                            - flatten_expr(client_tree).astype(jnp.float32))

        self._unflatten_expr = unflatten_expr
        self.flatten_expr = flatten_expr
        self.flatten_rows_expr = flatten_rows_expr
        self.split_expr = split_expr
        self.cast_expr = cast_expr
        self.blend_row_expr = blend_row_expr
        self.delta_row_expr = delta_row_expr
        self.blend_runs_expr = blend_runs_expr
        self.delta_runs_expr = delta_runs_expr
        self._flatten = jax.jit(flatten_expr)
        self._unflatten = jax.jit(unflatten_expr)
        dn = (0,) if donate else ()
        self._blend_one = jax.jit(blend_one, donate_argnums=dn)
        self._blend_many = jax.jit(blend_many, donate_argnums=dn)
        self._mac_rows = jax.jit(mac_rows, donate_argnums=dn)
        self._mac_cids = jax.jit(mac_cids, donate_argnums=dn)
        self._blend_row = jax.jit(blend_row, donate_argnums=dn)
        self._delta_row = jax.jit(delta_row)
        self._delta_one = jax.jit(delta_one)

    # -- flat store ---------------------------------------------------------
    @property
    def unflatten_expr(self):
        """The traceable (non-jitted) unflatten expression,
        ``cast_expr(split_expr(flat))`` — programs close over it to read
        a flat row as the model's pytree (the sweep plane's evals)."""
        return self._unflatten_expr

    def flatten(self, tree) -> jnp.ndarray:
        """Pytree -> contiguous (n,) storage buffer."""
        return self._flatten(tree)

    def unflatten(self, flat: jnp.ndarray):
        """Contiguous (n,) buffer -> pytree view (leaf dtypes restored)."""
        return self._unflatten(flat)

    # -- fused blends over the flat store -----------------------------------
    def blend_flat(self, g_flat, client_tree, beta
                   ) -> Tuple[jnp.ndarray, Any]:
        """Single-event eq. (3) on the flat store; returns (flat, tree)."""
        coefs = jnp.stack([jnp.float32(beta), 1.0 - jnp.float32(beta)])
        return self._blend_one(g_flat, client_tree, coefs)

    def blend_trunk_flat(self, g_flat, client_trees: Sequence[Any],
                         betas: Sequence[float]
                         ) -> Tuple[jnp.ndarray, Any]:
        """Fold K sequential eq.-(3) blends into ONE C=K kernel launch.

        K is bucketed to the next power of two (padding with repeated
        zero-coefficient clients) so a server whose drained-trunk size
        fluctuates 1..M compiles at most log2(M) program variants instead
        of one per distinct K — each first-seen pytree structure would
        otherwise trace+compile while every requester waits.
        """
        if len(client_trees) != len(betas):
            raise ValueError("one beta per queued client update")
        if len(client_trees) == 1:
            return self.blend_flat(g_flat, client_trees[0], betas[0])
        c0, coefs = agg.fold_sequential_blends([float(b) for b in betas])
        K = len(client_trees)
        bucket = pow2_bucket(K)
        client_trees = tuple(client_trees) + \
            (client_trees[0],) * (bucket - K)
        coefs = np.concatenate((coefs, np.zeros(bucket - K)))
        cvec = jnp.asarray(np.concatenate(([c0], coefs)), jnp.float32)
        return self._blend_many(g_flat, client_trees, cvec)

    def weighted_sum_flat(self, coef0, g_flat, coefs,
                          client_trees: Sequence[Any]
                          ) -> Tuple[jnp.ndarray, Any]:
        """Baseline cycle (eq. 2/7): w ← c0·w + Σ c_m·w_m, one launch."""
        cvec = jnp.concatenate([
            jnp.reshape(jnp.asarray(coef0, jnp.float32), (1,)),
            jnp.asarray(coefs, jnp.float32)])
        return self._blend_many(g_flat, tuple(client_trees), cvec)

    # -- row-addressed blends over a (M, n) fleet buffer --------------------
    def blend_row_flat(self, g_flat, fleet_buf, cid, beta) -> jnp.ndarray:
        """Single-event eq. (3) against row ``cid`` of the stacked fleet
        buffer — the ``dynamic_slice`` happens inside the jitted program,
        so there is no per-event flatten and no host round-trip."""
        coefs = jnp.stack([jnp.float32(beta), 1.0 - jnp.float32(beta)])
        return self._blend_row(g_flat, fleet_buf, jnp.int32(cid), coefs)

    def blend_rows_flat(self, g_flat, rows: jnp.ndarray,
                        betas: Sequence[float]) -> jnp.ndarray:
        """Trunk of K sequential eq.-(3) blends where the K client models
        are ALREADY flat rows (K, n).  Same pow2 bucketing as
        ``blend_trunk_flat`` (zero-coefficient zero rows pad the trunk)."""
        K = rows.shape[0]
        if K != len(betas):
            raise ValueError("one beta per queued row")
        if K == 1:
            coefs = jnp.stack([jnp.float32(betas[0]),
                               1.0 - jnp.float32(betas[0])])
            return self._mac_rows(g_flat, rows, coefs)
        c0, coefs = agg.fold_sequential_blends([float(b) for b in betas])
        bucket = pow2_bucket(K)
        if bucket > K:
            rows = jnp.concatenate(
                [rows, jnp.zeros((bucket - K, self.n), rows.dtype)])
            coefs = np.concatenate((coefs, np.zeros(bucket - K)))
        cvec = jnp.asarray(np.concatenate(([c0], coefs)), jnp.float32)
        return self._mac_rows(g_flat, rows, cvec)

    def weighted_sum_rows_flat(self, coef0, g_flat, coefs,
                               rows: jnp.ndarray) -> jnp.ndarray:
        """Baseline cycle (eq. 2/7) where the M client models are the
        (M, n) fleet buffer itself: w ← c0·w + Σ c_m·rows[m]."""
        cvec = jnp.concatenate([
            jnp.reshape(jnp.asarray(coef0, jnp.float32), (1,)),
            jnp.asarray(coefs, jnp.float32)])
        return self._mac_rows(g_flat, rows, cvec)

    def blend_rows_fleet(self, g_flat, fleet_buf, cids: Sequence[int],
                         betas: Sequence[float]) -> jnp.ndarray:
        """Trunk of K sequential eq.-(3) blends whose K client models
        are rows of the (M, n) fleet buffer, addressed by cid and
        gathered inside the program — the ingest plane's row-batched
        blend entry (DESIGN.md §11; one launch per micro-batch).  Same
        pow2 bucketing and fold as ``blend_rows_flat`` (zero-coefficient
        repeats of ``cids[0]`` pad the trunk), and the same signature as
        ``ShardedRowEngine.blend_rows_fleet`` so callers are
        plane-agnostic."""
        if len(cids) != len(betas):
            raise ValueError("one beta per queued row")
        c0, coefs = agg.fold_sequential_blends([float(b) for b in betas])
        bucket = pow2_bucket(len(cids))
        pad = bucket - len(cids)
        coefs = np.concatenate((coefs, np.zeros(pad)))
        cids = np.concatenate((np.asarray(cids, np.int32),
                               np.full(pad, cids[0], np.int32)))
        cvec = jnp.asarray(np.concatenate(([c0], coefs)), jnp.float32)
        return self._mac_cids(g_flat, fleet_buf, jnp.asarray(cids), cvec)

    # -- FedOpt pseudo-gradients on the flat buffer -------------------------
    def delta_flat(self, g_flat, client_tree, scale) -> jnp.ndarray:
        """(n,) f32 pseudo-gradient scale·(w − w_client), one launch."""
        return self._delta_one(g_flat, client_tree, jnp.float32(scale))

    def delta_row_flat(self, g_flat, fleet_buf, cid, scale) -> jnp.ndarray:
        return self._delta_row(g_flat, fleet_buf, jnp.int32(cid),
                               jnp.float32(scale))

    # -- pytree-in / pytree-out conveniences --------------------------------
    def blend(self, global_tree, client_tree, beta):
        """Drop-in for ``aggregation.blend_pytree`` through the kernel."""
        _, tree = self.blend_flat(self.flatten(global_tree), client_tree,
                                  beta)
        return tree

    def blend_trunk(self, global_tree, client_trees, betas):
        _, tree = self.blend_trunk_flat(self.flatten(global_tree),
                                        client_trees, betas)
        return tree

    def weighted_sum(self, coef0, global_tree, coefs, client_trees):
        """Drop-in for ``aggregation.weighted_sum_pytrees``."""
        _, tree = self.weighted_sum_flat(coef0, self.flatten(global_tree),
                                         coefs, client_trees)
        return tree


# ---------------------------------------------------------------------------
# Shard-aware row addressing over a fleet-sharded buffer (DESIGN.md §6)
# ---------------------------------------------------------------------------
class ShardedRowEngine:
    """Row-addressed blends against a ``fleet``-sharded (M_pad, n) buffer.

    Wraps a base :class:`AggEngine` (which fixes the flat layout and the
    plain/replicated blends) and reimplements ONLY the row-addressed
    variants as ``shard_map`` programs over ``layout`` (a
    ``sharding.specs.FleetLayout``): the global flat model is replicated,
    the fleet buffer is row-partitioned, and a global row index resolves
    to (shard, local-row) *inside* the program — the owning shard
    contributes its row through a ``psum``, so the fleet is never
    gathered.  Everything not listed here delegates to the base engine
    (``flatten``/``unflatten``, the pytree blends, the replicated-rows
    trunk blend the async runtime uses).

    With the base engine in ``kernel`` mode the fleet-wide weighted sum
    runs the Pallas MAC per shard (c0 pre-divided by D so the psum over
    the replicated global restores it — same trick as
    ``core.shardmap_agg``); the single-row blends stay jnp (a C=1 MAC
    after the psum is one fused elementwise op either way).
    """

    def __init__(self, engine: AggEngine, mesh, layout):
        from jax.sharding import PartitionSpec as P

        from repro.sharding.specs import FLEET_AXIS, fleet_buffer_spec

        self.base = engine
        self.mesh = mesh
        self.layout = layout
        ax = FLEET_AXIS
        D = layout.D
        m_loc = layout.rows_per_shard
        buf_spec = fleet_buffer_spec()
        storage = engine.storage_dtype
        kern = engine.kern
        use_kernel = engine.mode == "kernel"

        def owned_row(local, cid):
            """psum-gather row ``cid`` (f32) from its owning shard."""
            shard = cid // m_loc
            row = jax.lax.dynamic_slice_in_dim(
                local, cid - shard * m_loc, 1, axis=0)[0]
            mine = jax.lax.axis_index(ax) == shard
            return jax.lax.psum(
                jnp.where(mine, row.astype(jnp.float32), 0.0), ax)

        def blend_row_shard(g, local, cid, coefs):
            row = owned_row(local, cid)
            if use_kernel:
                return kern(g, row.astype(storage)[None], coefs)
            acc = (coefs[0] * g.astype(jnp.float32) + coefs[1] * row)
            return acc.astype(storage)

        def delta_row_shard(g, local, cid, scale):
            return scale * (g.astype(jnp.float32) - owned_row(local, cid))

        def weighted_sum_shard(g, local, c0, c_local):
            if use_kernel:
                cvec = jnp.concatenate([c0[None] / D, c_local])
                partial = kern(g, local, cvec)
                return jax.lax.psum(
                    partial.astype(jnp.float32), ax).astype(storage)
            partial = jnp.tensordot(c_local, local.astype(jnp.float32),
                                    axes=(0, 0))
            total = jax.lax.psum(partial, ax)
            return (c0 * g.astype(jnp.float32) + total).astype(storage)

        def blend_rows_shard(g, local, c0, coefs, cids):
            """Folded trunk over fleet rows: each shard contributes the
            coefficient-weighted rows it owns."""
            shard = cids // m_loc
            rows = local[cids - shard * m_loc]            # (K, n) gather
            mask = (jax.lax.axis_index(ax) == shard).astype(jnp.float32)
            partial = jnp.tensordot(coefs * mask, rows.astype(jnp.float32),
                                    axes=(0, 0))
            total = jax.lax.psum(partial, ax)
            return (c0 * g.astype(jnp.float32) + total).astype(storage)

        sm = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)
        # NO donation here: every program returns a replicated (n,) global,
        # which can never alias the sharded (M_pad, n) buffer, and callers
        # (run_fedavg's next train_all, the parity oracles) keep reading
        # the buffer after the blend
        self._blend_row = jax.jit(sm(
            blend_row_shard, in_specs=(P(), buf_spec, P(), P()),
            out_specs=P()))
        self._delta_row = jax.jit(sm(
            delta_row_shard, in_specs=(P(), buf_spec, P(), P()),
            out_specs=P()))
        self._weighted_sum = jax.jit(sm(
            weighted_sum_shard, in_specs=(P(), buf_spec, P(), P(ax)),
            out_specs=P()))
        self._blend_rows = jax.jit(sm(
            blend_rows_shard, in_specs=(P(), buf_spec, P(), P(), P()),
            out_specs=P()))

    # anything not shard-aware (flatten/unflatten, pytree blends, the
    # replicated-rows trunk the async runtime feeds) is the base engine's
    def __getattr__(self, name):
        return getattr(self.base, name)

    def blend_row_flat(self, g_flat, fleet_buf, cid, beta) -> jnp.ndarray:
        coefs = jnp.stack([jnp.float32(beta), 1.0 - jnp.float32(beta)])
        return self._blend_row(g_flat, fleet_buf, jnp.int32(cid), coefs)

    def delta_row_flat(self, g_flat, fleet_buf, cid, scale) -> jnp.ndarray:
        return self._delta_row(g_flat, fleet_buf, jnp.int32(cid),
                               jnp.float32(scale))

    def weighted_sum_rows_flat(self, coef0, g_flat, coefs,
                               rows: jnp.ndarray) -> jnp.ndarray:
        """Fleet-wide eq. (2/7) where ``rows`` IS the sharded (M_pad, n)
        buffer; ``coefs`` has one entry per REAL client and is zero-padded
        to M_pad here (padded rows never contribute)."""
        coefs = np.asarray(coefs, np.float32)
        pad = self.layout.M_pad - coefs.shape[0]
        if pad:
            coefs = np.concatenate([coefs, np.zeros(pad, np.float32)])
        return self._weighted_sum(g_flat, rows, jnp.float32(coef0),
                                  jnp.asarray(coefs))

    def blend_rows_fleet(self, g_flat, fleet_buf, cids: Sequence[int],
                         betas: Sequence[float]) -> jnp.ndarray:
        """Trunk of K sequential eq.-(3) blends whose K client models are
        rows of the sharded fleet buffer (addressed by global cid); K is
        pow2-bucketed with zero-coefficient repeats of cids[0]."""
        if len(cids) != len(betas):
            raise ValueError("one beta per queued row")
        c0, coefs = agg.fold_sequential_blends([float(b) for b in betas])
        bucket = pow2_bucket(len(cids))
        pad = bucket - len(cids)
        coefs = np.concatenate((coefs, np.zeros(pad))).astype(np.float32)
        cids = np.concatenate((np.asarray(cids, np.int32),
                               np.full(pad, cids[0], np.int32)))
        return self._blend_rows(g_flat, fleet_buf, jnp.float32(c0),
                                jnp.asarray(coefs), jnp.asarray(cids))


# ---------------------------------------------------------------------------
# Slot-addressed row blends over a paged (P, n) active-set pool (§12)
# ---------------------------------------------------------------------------
class PagedRowEngine:
    """Row-addressed blends against the (P, n) active-slot pool of a
    :class:`~repro.core.client_plane.PagedClientPlane`.

    Wraps the base :class:`AggEngine` (which fixes the flat layout and
    every traceable expression) and reimplements ONLY the row-addressed
    entry points: a global cid resolves to its device slot HOST-side
    (one slot-table lookup — the paged plane guarantees residency before
    any blend), and the base engine's programs then run unchanged
    against the pool.  The fleet-wide weighted sum (the FedAvg-cycle
    consumer, which needs every row) flushes the pool and accumulates
    over the host arena in bounded-size chunks instead of gathering an
    (M, n) device buffer that paged mode exists to avoid.

    Everything else — ``flatten``/``unflatten``, the traceable
    ``blend_row_expr``/``delta_row_expr`` the compiled scan inlines, the
    pytree blends — delegates to the base engine, so
    ``getattr(plane.engine, "base", plane.engine)`` keeps resolving the
    raw engine exactly as it does for :class:`ShardedRowEngine`.
    """

    def __init__(self, engine: AggEngine, plane):
        self.base = engine
        self._plane = plane

    def __getattr__(self, name):
        return getattr(self.base, name)

    def _slot(self, cid) -> int:
        return self._plane.slot_index(int(cid))

    def blend_row_flat(self, g_flat, fleet_buf, cid, beta) -> jnp.ndarray:
        return self.base.blend_row_flat(g_flat, fleet_buf,
                                        self._slot(cid), beta)

    def delta_row_flat(self, g_flat, fleet_buf, cid, scale) -> jnp.ndarray:
        return self.base.delta_row_flat(g_flat, fleet_buf,
                                        self._slot(cid), scale)

    def blend_rows_fleet(self, g_flat, fleet_buf, cids: Sequence[int],
                         betas: Sequence[float]) -> jnp.ndarray:
        slots = [self._slot(c) for c in cids]
        return self.base.blend_rows_fleet(g_flat, fleet_buf, slots, betas)

    def weighted_sum_rows_flat(self, coef0, g_flat, coefs,
                               rows: jnp.ndarray) -> jnp.ndarray:
        """Fleet-wide eq. (2/7) where ``rows`` is the (P, n) pool:
        flush, then a chunked f32 MAC over the arena (≤1e-5 of the dense
        single-launch tensordot — partial-sum reordering only)."""
        return self._plane.fleet_weighted_sum(coef0, g_flat, coefs, rows)


# ---------------------------------------------------------------------------
# Engine cache — one engine per (tree-structure, options)
# ---------------------------------------------------------------------------
_ENGINES: Dict[Any, AggEngine] = {}


def engine_for(template, *, block_rows: Optional[int] = None,
               interpret: Optional[bool] = None, mode: Optional[str] = None,
               storage_dtype=None) -> AggEngine:
    """Fetch (or build) the cached engine for ``template``'s structure."""
    leaves, treedef = jax.tree.flatten(template)
    key = (treedef,
           tuple(tuple(l.shape) for l in leaves),
           tuple(str(jnp.dtype(l.dtype)) for l in leaves),
           block_rows, interpret, mode,
           None if storage_dtype is None else str(jnp.dtype(storage_dtype)))
    eng = _ENGINES.get(key)
    if eng is None:
        eng = AggEngine(template, block_rows=block_rows,
                        interpret=interpret, mode=mode,
                        storage_dtype=storage_dtype)
        _ENGINES[key] = eng
    return eng


# ---------------------------------------------------------------------------
# Per-leaf twin for sharded parameter trees (GSPMD data plane)
# ---------------------------------------------------------------------------
def weighted_sum_leaves(coef0, global_tree, coefs, clients_stacked_tree):
    """w ← c0·w + Σ_c c_c·w_c with a leading client dim on every leaf.

    Used by the fused SPMD step (``core/distributed.py``): leaves there are
    ZeRO/client-sharded, so they must stay separate ``tensordot``s that
    GSPMD lowers to one weighted all-reduce each — flattening into the
    engine's contiguous buffer would force a resharding gather.  The math
    is the engine's, the layout is the partitioner's.
    """
    c0 = jnp.asarray(coef0, jnp.float32)
    cc = jnp.asarray(coefs, jnp.float32)

    def leaf(g, w):
        acc = c0 * g.astype(jnp.float32)
        acc = acc + jnp.tensordot(cc, w.astype(jnp.float32), axes=(0, 0))
        return acc.astype(g.dtype)

    return jax.tree.map(leaf, global_tree, clients_stacked_tree)
