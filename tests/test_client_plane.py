"""Tests for the fused client-fleet training plane (core/client_plane.py,
docs/DESIGN.md §4) and its row-addressed engine blends:

* the scheduler's event trace is deterministic (pinned for a fixed
  seed/fleet — the precomputation the plane's staged batching relies on);
* run_afl / run_fedavg histories with ``use_client_plane=True`` match the
  per-minibatch reference path to 1e-5, at f32 (the paper CNN) and bf16
  (a flat toy fleet);
* the engine's row-addressed blends equal the per-leaf oracles;
* the threaded async runtime works end-to-end on flat rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core.afl import run_afl
from repro.core.agg_engine import AggEngine, engine_for
from repro.core.client_plane import ClientPlane
from repro.core.scheduler import AFLScheduler, ClientSpec, make_fleet
from repro.core.sfl import run_fedavg
from repro.core.tasks import CNNTask
from repro.models import cnn as cnn_mod


# ---------------------------------------------------------------------------
# Scheduler trace precomputation: events() is deterministic
# ---------------------------------------------------------------------------
# make_fleet(6, tau=1.0, hetero_a=6.0, samples=[60..160], adaptive, seed=7):
# cid=0 tau=6.000000 K=1 | cid=1 tau=4.990776 K=1 | cid=2 tau=4.014216 K=1
# cid=3 tau=1.497081 K=2 | cid=4 tau=1.712280 K=2 | cid=5 tau=1.000000 K=3
_PINNED_TRACE = [
    (3, 1, 3.294162), (5, 2, 3.494162), (4, 3, 3.724560),
    (2, 4, 4.314216), (1, 5, 5.290776), (0, 6, 6.300000),
    (3, 6, 6.588323), (5, 6, 6.794162), (4, 6, 7.449120),
    (2, 6, 8.628433), (3, 4, 9.882485), (5, 4, 10.094162),
    (1, 8, 10.581551), (4, 5, 11.173680), (0, 9, 12.600000),
    (2, 6, 12.942649), (3, 6, 13.176647), (5, 6, 13.394162),
    (4, 5, 14.898240), (1, 7, 15.872327), (3, 4, 16.470809),
    (5, 4, 16.694162), (2, 7, 17.256866), (4, 5, 18.622799),
    (0, 10, 18.900000), (3, 5, 19.764970), (5, 5, 19.994162),
    (1, 8, 21.163102), (2, 6, 21.571082), (4, 6, 22.347359),
]


def test_scheduler_trace_pinned():
    """AFLScheduler.events() is a pure function of (fleet, tau_u, tau_d):
    the full (cid, staleness, t_complete) trace for a fixed seed/fleet is
    pinned, so staged-batch precomputation can rely on it."""
    fleet = make_fleet(6, tau=1.0, hetero_a=6.0,
                       samples_per_client=[60, 80, 100, 120, 140, 160],
                       adaptive=True, seed=7)
    sched = AFLScheduler(fleet, tau_u=0.2, tau_d=0.1)
    evs = list(sched.events(len(_PINNED_TRACE)))
    assert len(evs) == len(_PINNED_TRACE)
    for e, (cid, stale, t) in zip(evs, _PINNED_TRACE):
        assert e.cid == cid
        assert e.staleness == stale
        assert abs(e.t_complete - t) < 1e-6
    # replaying the generator yields the identical trace
    evs2 = list(sched.events(len(_PINNED_TRACE)))
    assert [(e.cid, e.staleness, e.t_complete) for e in evs] == \
        [(e.cid, e.staleness, e.t_complete) for e in evs2]


# ---------------------------------------------------------------------------
# f32 parity: the paper CNN, plane on vs per-minibatch reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cnn_setup():
    task = CNNTask(iid=True, num_clients=5, train_n=600, test_n=200,
                   local_batches_per_step=3)
    fleet = make_fleet(5, tau=1.0, hetero_a=4.0,
                       samples_per_client=task.num_samples(), seed=1)
    return task, fleet, task.init_params(), task.client_plane(fleet)


def _tree_maxdiff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def test_run_afl_plane_parity_f32(cnn_setup):
    task, fleet, p0, plane = cnn_setup
    kw = dict(algorithm="csmaafl", iterations=12, tau_u=0.1, tau_d=0.1,
              gamma=0.4, eval_fn=task.eval_fn, eval_every=4)
    r_on = run_afl(p0, fleet, None, client_plane=plane, **kw)
    r_off = run_afl(p0, fleet, task.local_train_fn,
                    client_plane=plane, use_client_plane=False, **kw)
    assert _tree_maxdiff(r_on.params, r_off.params) <= 1e-5
    np.testing.assert_allclose(r_on.betas, r_off.betas, atol=1e-6)
    assert r_on.history.times == r_off.history.times
    np.testing.assert_allclose(r_on.history.series("accuracy"),
                               r_off.history.series("accuracy"), atol=1e-5)


def test_run_fedavg_plane_parity_f32(cnn_setup):
    task, fleet, p0, plane = cnn_setup
    kw = dict(rounds=3, tau_u=0.1, tau_d=0.1, eval_fn=task.eval_fn)
    w_on, h_on = run_fedavg(p0, fleet, None, client_plane=plane, **kw)
    w_off, h_off = run_fedavg(p0, fleet, task.local_train_fn, **kw)
    assert _tree_maxdiff(w_on, w_off) <= 1e-5
    assert h_on.times == h_off.times
    np.testing.assert_allclose(h_on.series("accuracy"),
                               h_off.series("accuracy"), atol=1e-5)


def test_run_afl_baseline_plane_still_equals_fedavg():
    """C1 exactness survives the client plane: baseline AFL over M
    iterations == one FedAvg round, both fully fused.  (C1 requires
    seed-independent local data, so this uses a fixed-target toy fleet —
    same construction as the pre-plane C1 tests.)"""
    M, D = 4, 41
    rng = np.random.default_rng(5)
    targets = rng.normal(size=(M, D)).astype(np.float32)
    w0 = jnp.asarray(rng.normal(size=D).astype(np.float32))
    fleet = make_fleet(M, tau=1.0, hetero_a=4.0,
                       samples_per_client=[60 + 20 * m for m in range(M)],
                       adaptive=False, seed=0)
    eng = AggEngine(w0)

    def batch_fn(cid, num_steps, seed_):       # seed-independent data
        return np.broadcast_to(targets[cid], (num_steps, D)).copy()

    def step(flat, t):
        return flat - 0.2 * (flat - t)

    plane = ClientPlane(eng, fleet, step, batch_fn)
    w_sfl, _ = run_fedavg(w0, fleet, None, client_plane=plane, rounds=2,
                          tau_u=0.2, tau_d=0.1)
    res = run_afl(w0, fleet, None, client_plane=plane,
                  algorithm="afl_baseline", iterations=2 * M,
                  tau_u=0.2, tau_d=0.1)
    assert _tree_maxdiff(res.params, w_sfl) <= 1e-5


# ---------------------------------------------------------------------------
# bf16 parity: flat toy fleet (elementwise local SGD, bf16 storage)
# ---------------------------------------------------------------------------
def _bf16_toy(M, D, seed=0):
    """Per-client pull-toward-target task on bf16 params.  The plane's
    step_fn and the reference local_train_fn apply the SAME elementwise
    update to the SAME staged batches, so parity is exact even at bf16."""
    rng = np.random.default_rng(seed)
    w0 = jnp.asarray(rng.normal(size=D), jnp.bfloat16)
    batches_cache = {}

    def batch_fn(cid, num_steps, seed_):
        key = (cid, num_steps, seed_)
        if key not in batches_cache:
            r = np.random.default_rng((seed_ * 131 + cid) % (2 ** 31))
            batches_cache[key] = jnp.asarray(
                r.normal(size=(num_steps, D)), jnp.bfloat16)
        return batches_cache[key]

    def step(flat, target):
        return (flat.astype(jnp.float32)
                - 0.25 * (flat.astype(jnp.float32)
                          - target.astype(jnp.float32))
                ).astype(jnp.bfloat16)

    def local_train(params, cid, steps, seed_):
        for t in batch_fn(cid, steps, seed_):
            params = step(params, t)
        return params

    return w0, step, batch_fn, local_train


@pytest.mark.parametrize("runner", ["afl", "fedavg"])
def test_plane_parity_bf16(runner):
    M, D = 4, 97          # ragged D: exercises the flat-tile zero padding
    w0, step, batch_fn, local_train = _bf16_toy(M, D)
    fleet = make_fleet(M, tau=1.0, hetero_a=4.0,
                       samples_per_client=[60 + 20 * m for m in range(M)],
                       adaptive=True, max_steps=3, seed=2)
    engine = AggEngine(w0, storage_dtype=jnp.bfloat16)
    plane = ClientPlane(engine, fleet, step, batch_fn)

    def eval_fn(p):
        return {"s": float(jnp.sum(jnp.asarray(p, jnp.float32)))}

    if runner == "afl":
        kw = dict(algorithm="csmaafl", iterations=24, tau_u=0.1, tau_d=0.1,
                  gamma=0.4, eval_fn=eval_fn, eval_every=6)
        r_on = run_afl(w0, fleet, None, client_plane=plane, **kw)
        r_off = run_afl(w0, fleet, local_train, **kw)
        on, off = r_on.history.series("s"), r_off.history.series("s")
        p_on, p_off = r_on.params, r_off.params
    else:
        kw = dict(rounds=4, tau_u=0.1, tau_d=0.1, eval_fn=eval_fn)
        p_on, h_on = run_fedavg(w0, fleet, None, client_plane=plane, **kw)
        p_off, h_off = run_fedavg(w0, fleet, local_train, **kw)
        on, off = h_on.series("s"), h_off.series("s")
    np.testing.assert_allclose(on, off, atol=1e-5)
    assert _tree_maxdiff(p_on, p_off) <= 1e-5


# ---------------------------------------------------------------------------
# Row-addressed engine blends == per-leaf oracles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-6),
                                        (jnp.bfloat16, 2e-2)])
def test_blend_row_matches_blend_pytree(key, dtype, atol):
    n, M = 301, 5
    g = jax.random.normal(key, (n,), dtype)
    eng = AggEngine(g, storage_dtype=dtype)
    fleet_buf = jnp.stack([g * (0.3 * m - 1.0) + m for m in range(M)])
    for cid in (0, 3):
        out = eng.blend_row_flat(eng.flatten(g), fleet_buf, cid, 0.7)
        ref = agg.blend_pytree(g, fleet_buf[cid], 0.7)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("K", [3, 4])     # non-pow2 K exercises bucketing
def test_blend_rows_matches_sequential(key, K):
    n = 257
    g = jax.random.normal(key, (n,))
    eng = AggEngine(g)
    rows = jnp.stack([g * 0.5 + m for m in range(K)])
    betas = [0.9, 0.6, 0.8, 0.7][:K]
    out = eng.blend_rows_flat(eng.flatten(g), rows, betas)
    ref = g
    for m, b in zip(rows, betas):
        ref = agg.blend_pytree(ref, m, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_weighted_sum_rows_matches_reference(key):
    n, M = 130, 4
    g = jax.random.normal(key, (n,))
    eng = AggEngine(g)
    rows = jnp.stack([g + m for m in range(M)])
    alpha = agg.sfl_alpha([60, 80, 100, 120])
    out = eng.weighted_sum_rows_flat(0.0, eng.flatten(g), list(alpha), rows)
    ref = agg.weighted_sum_pytrees(0.0, g, list(alpha), list(rows))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_delta_row_is_fedopt_pseudo_gradient(key):
    n = 64
    g = jax.random.normal(key, (n,))
    eng = AggEngine(g)
    fleet_buf = jnp.stack([g + 1.0, g - 2.0])
    pg = eng.delta_row_flat(eng.flatten(g), fleet_buf, 1, 0.5)
    np.testing.assert_allclose(np.asarray(pg), 0.5 * 2.0 * np.ones(n),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# ClientPlane mechanics: bucketing + masking
# ---------------------------------------------------------------------------
def test_plane_bucketing_pads_with_noop_steps():
    """A 5-batch round buckets to 8 scan steps; the 3 padded steps must
    leave the row untouched (valid-mask), so the result equals the plain
    5-step loop."""
    D = 33
    w0 = jnp.arange(D, dtype=jnp.float32)
    fleet = [ClientSpec(cid=0, tau_compute=1.0, num_samples=10,
                        local_steps=5)]
    eng = AggEngine(w0)

    def batch_fn(cid, num_steps, seed):
        r = np.random.default_rng(seed)
        return r.normal(size=(num_steps, D)).astype(np.float32)

    def step(flat, t):
        return flat - 0.1 * (flat - t)

    plane = ClientPlane(eng, fleet, step, batch_fn)
    out = plane.local_train_flat(eng.flatten(w0), 0, 5, seed=3)
    ref = w0
    for t in batch_fn(0, 5, 3):
        ref = step(ref, jnp.asarray(t))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


# ---------------------------------------------------------------------------
# Tree carry: the scan carries the model per leaf, the row only at its edges
# ---------------------------------------------------------------------------
def _flat_carry_scan(engine, train_x, train_y, lr, unroll):
    """The oracle: local SGD with the (n,) row as the scan carry and the
    gradient taken through the engine's unflatten expression at every
    step — the plane's scan before it carried the tree."""
    unflatten = engine.unflatten_expr

    def step(flat, idx):
        batch = {"images": train_x[idx], "labels": train_y[idx]}
        grad = jax.grad(lambda f: cnn_mod.loss_fn(unflatten(f), batch))(flat)
        return flat - lr * grad

    def scan_train(flat, batches, valid):
        def body(w, xs):
            b, v = xs
            return jnp.where(v, step(w, b).astype(w.dtype), w), None
        out, _ = jax.lax.scan(body, flat, (batches, valid), unroll=unroll)
        return out

    return scan_train


@pytest.mark.parametrize("path", ["row", "fleet"])
@pytest.mark.parametrize("storage", [jnp.float32, jnp.bfloat16])
def test_tree_carried_scan_matches_flat_carry(cnn_setup, storage, path):
    """The paper CNN's retrain (one row) and fleet round (vmapped rows)
    give bit-identical rows to the flat-carried scan, at f32 storage and
    at bf16 storage, where every step still rounds through bf16."""
    task, fleet, p0, cnn_plane = cnn_setup
    eng = engine_for(jax.eval_shape(task.init_params),
                     storage_dtype=storage)
    plane = ClientPlane(eng, fleet, cnn_plane.step_fn, cnn_plane.batch_fn)
    oracle = _flat_carry_scan(eng, task._train_x, task._train_y, task.lr,
                              plane.unroll)
    g = eng.flatten(p0)
    if path == "row":
        batches, valid = plane._stage_one(3, 1, seed=5)   # 3 of 4 valid
        out = plane.local_train_flat(g, 3, 1, seed=5)
        ref = jax.jit(oracle)(g, batches, valid)
    else:
        batches, valid = plane._stage_fleet(seed=5)
        # two clients' first four steps (3 valid each): the flat carry's
        # bf16 program is slow to compile unrolled
        batches, valid = batches[1:3, :4], valid[1:3, :4]
        out = plane._fleet_rows(g, batches, valid)
        ref = jax.jit(jax.vmap(oracle, in_axes=(None, 0, 0)))(
            g, batches, valid)
    assert out.dtype == ref.dtype == storage
    assert out.shape == ref.shape
    assert not np.array_equal(np.asarray(out, np.float32),
                              np.asarray(g, np.float32)[None])
    assert np.asarray(out).tobytes() == np.asarray(ref).tobytes()


def test_scan_carries_the_paper_cnn_per_leaf(cnn_setup):
    """The rolled scan's while loop carries one array per leaf of the
    paper CNN, never the f32[n] flat row; so does the fleet round's, one
    leaf per client, never the (M, n) rows it writes after the loop."""
    task, fleet, p0, cnn_plane = cnn_setup
    plane = ClientPlane(cnn_plane.engine, fleet, cnn_plane.step_fn,
                        cnn_plane.batch_fn, unroll=1)
    n, M = plane.engine.n, len(fleet)
    g = plane.engine.flatten(p0)
    batches, valid = plane._stage_one(0, 1, seed=5)
    text = jax.jit(plane._scan_train).lower(
        g, batches, valid, plane._lstep).as_text()
    loops = [ln for ln in text.splitlines() if "stablehlo.while(" in ln]
    assert len(loops) == 1
    carried = loops[0].split(" : ", 1)[1]
    for shape in plane.engine.shapes:
        assert f"tensor<{'x'.join(map(str, shape))}xf32>" in carried
    assert f"tensor<{n}xf32>" not in carried
    batches, valid = plane._stage_fleet(seed=5)
    text = plane._train_all.lower(plane._new_rows(M), g, batches, valid,
                                  plane._lstep).as_text()
    loops = [ln for ln in text.splitlines() if "stablehlo.while(" in ln]
    assert len(loops) == 1
    carried = loops[0].split(" : ", 1)[1]
    for shape in plane.engine.shapes:
        assert f"tensor<{'x'.join(map(str, (M,) + shape))}xf32>" in carried
    assert f"{n}xf32>" not in carried
    assert f"tensor<{M}x{n}xf32>" in text


@pytest.mark.parametrize("storage", [jnp.float32, jnp.bfloat16])
def test_flatten_rows_writes_each_client_into_its_row(storage):
    """``flatten_rows_expr`` writes a client-stacked tree into the (C, n)
    rows it is handed: row c is ``flatten_expr`` of client c's leaves,
    bit for bit, whatever the rows held before."""
    tmpl = {"w": jnp.zeros((3, 4), jnp.float32),
            "b": jnp.zeros((5,), jnp.float32)}
    eng = AggEngine(tmpl, storage_dtype=storage)
    rng = np.random.default_rng(0)
    trees = {k: jnp.asarray(rng.normal(size=(6,) + v.shape), storage)
             for k, v in tmpl.items()}
    out = jax.jit(eng.flatten_rows_expr)(jnp.full((6, eng.n), 7.0, storage),
                                        trees)
    want = jax.vmap(eng.flatten_expr)(trees)
    assert out.dtype == want.dtype == storage
    assert np.asarray(out).tobytes() == np.asarray(want).tobytes()


def test_split_and_flatten_round_trip_the_row():
    """``split_expr`` and ``flatten_expr`` move a storage row between its
    flat and tree forms bit for bit, the tree in the storage dtype;
    ``cast_expr`` then gives the template's leaf dtypes, which is
    ``unflatten_expr``."""
    tmpl = {"a": jnp.zeros((3, 4), jnp.float32),
            "b": jnp.zeros((5,), jnp.bfloat16)}
    eng = AggEngine(tmpl, storage_dtype=jnp.bfloat16)
    flat = jnp.asarray(np.random.default_rng(0).normal(size=eng.n),
                       jnp.bfloat16)
    tree = eng.split_expr(flat)
    assert {k: (v.shape, v.dtype) for k, v in tree.items()} == \
        {"a": ((3, 4), jnp.bfloat16), "b": ((5,), jnp.bfloat16)}
    assert np.asarray(eng.flatten_expr(tree)).tobytes() == \
        np.asarray(flat).tobytes()
    params = eng.cast_expr(tree)
    assert params["a"].dtype == jnp.float32
    for k, v in eng.unflatten_expr(flat).items():
        assert v.dtype == params[k].dtype
        np.testing.assert_array_equal(np.asarray(v), np.asarray(params[k]))


def test_plane_train_row_updates_only_target_row(cnn_setup):
    task, fleet, p0, plane = cnn_setup
    g = plane.flatten(p0)
    buf = plane.init_fleet(g, seed=11)
    buf2 = plane.train_row(buf, g, 2, 1, seed=12)
    assert buf2.shape == (len(fleet), plane.engine.n)
    for m in range(len(fleet)):
        same = np.allclose(np.asarray(buf2[m]), np.asarray(buf[m]))
        assert same == (m != 2)


def test_cnn_programs_take_the_data_as_arguments(cnn_setup):
    """The CNN task's device data enters the plane's programs and the
    sweep's eval as arguments: closed over, XLA embeds it as a constant
    in every compiled variant (188 MB of images per program at the
    paper's 60k samples)."""
    task, fleet, p0, plane = cnn_setup
    g = plane.flatten(p0)
    batches, valid = plane._stage_fleet(seed=11)
    eval_flat = task.eval_flat_fn(plane.engine)
    for data, lowered in [
            (task._train_x, plane._train_all.lower(
                plane._new_rows(len(fleet)), g, batches, valid,
                plane._lstep)),
            (task.test_x, jax.jit(lambda ev, w: ev(w)).lower(eval_flat, g))]:
        text = lowered.as_text()
        shape = "x".join(map(str, data.shape))
        assert f"tensor<{shape}xf32>" in text
        assert len(text) < data.nbytes


def test_cnn_batches_and_indices_agree():
    """batch_indices is the single source of batch order: materialized
    batches must be exactly the indexed rows of the shard."""
    task = CNNTask(iid=True, num_clients=3, train_n=300, test_n=50)
    c = task.clients[1]
    idx = c.batch_indices(5, 7, seed=9)
    bs = c.batches(5, 7, seed=9)
    assert idx.shape == (7, 5)
    for row, b in zip(idx, bs):
        np.testing.assert_array_equal(c.images[c.indices[row]], b["images"])
        np.testing.assert_array_equal(c.labels[c.indices[row]], b["labels"])
    # the staged-plane path reads the same rows from the full arrays
    gidx = task._global_batch_indices(1, 1, seed=9)
    np.testing.assert_array_equal(
        c.images[gidx[0]], bs[0]["images"])


# ---------------------------------------------------------------------------
# Threaded async runtime on flat rows
# ---------------------------------------------------------------------------
def test_async_runtime_with_plane(cnn_setup):
    from repro.core.async_runtime import run_async

    task, fleet, p0, plane = cnn_setup
    params, server, stats = run_async(
        p0, fleet, None, rounds_per_client=3, time_scale=0.002,
        client_plane=plane)
    assert server.j == len(fleet) * 3
    assert len(server.betas) == server.j
    assert sum(server.trunk_sizes) == server.j
    for cid, iters in stats.items():
        assert len(iters) == 3
        assert all(a < b for a, b in zip(iters, iters[1:]))
    acc = task.eval_fn(params)["accuracy"]
    assert np.isfinite(acc)
