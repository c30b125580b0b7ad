"""Tests for the batched sweep plane (core/sweep_plane.py, DESIGN.md §8):

* scenario registry / resolution and the data.federated partitioner
  registry (incl. the Dirichlet ``min_per_client`` rebalance);
* THE acceptance grid: 12 runs (3 scenarios x 4 seeds) at M=64 on the
  f32 paper CNN execute as ONE structure group in ≤ #buckets + 2
  launches (no eval) / with per-run history AND final-params parity
  ≤ 1e-5 against 12 individual ``compiled_loop=True`` runs (with eval);
* bf16 toy grid parity, including the §III-B baseline's every-M
  broadcast and the FedOpt server-optimizer path, run-batched;
* structure-divergent traces (adaptive-K fleets) fall back to smaller
  groups — same parity, more groups; ``sub_batch`` splits a group's
  launches without changing the math;
* ``Scenario.fleet_seed`` pins the device population across seeds (one
  scheduler simulation per scenario, identical timelines);
* the run-batched engine/plane primitives match their single-run twins
  (``blend_runs_expr`` / ``delta_runs_expr`` / ``train_all_runs``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import event_trace as et
from repro.core import sweep_plane as sp
from repro.core.afl import run_afl
from repro.core.agg_engine import AggEngine
from repro.core.client_plane import ClientPlane
from repro.core.tasks import CNNTask
from repro.data import federated as fd


def _maxdiff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float32)
                                   - np.asarray(y, np.float32))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _solo(task_or_w0, run, iterations, **kw):
    sc = run.scenario
    p0 = (task_or_w0.init_params(run.seed)
          if hasattr(task_or_w0, "init_params") else task_or_w0)
    return run_afl(p0, run.plane.fleet, None, algorithm=sc.algorithm,
                   iterations=iterations, tau_u=sc.tau_u, tau_d=sc.tau_d,
                   gamma=sc.gamma, mu_momentum=sc.mu_momentum,
                   max_staleness=sc.max_staleness, client_plane=run.plane,
                   compiled_loop=True, seed=run.seed, **kw)


# ---------------------------------------------------------------------------
# Registries
# ---------------------------------------------------------------------------
def test_scenario_registry_and_resolution():
    assert {"paper_iid", "paper_noniid", "dirichlet_skew", "uplink_bound",
            "adaptive_k", "baseline_cycle"} <= set(sp.SCENARIOS)
    assert sp.resolve_scenario("paper_iid") is sp.get_scenario("paper_iid")
    # dict entries override a registered base without mutating it
    over = sp.resolve_scenario({"name": "paper_iid", "gamma": 0.7,
                                "fleet_seed": 3})
    assert over.gamma == 0.7 and over.fleet_seed == 3
    assert sp.get_scenario("paper_iid").gamma == 0.4
    # inline scenarios need no registration
    inline = sp.resolve_scenario({"name": "mine", "algorithm": "afl_alpha"})
    assert inline.algorithm == "afl_alpha"
    with pytest.raises(KeyError, match="unknown scenario"):
        sp.get_scenario("nope")
    with pytest.raises(ValueError, match="unknown Scenario field"):
        sp.resolve_scenario({"name": "paper_iid", "gammma": 0.7})
    with pytest.raises(ValueError, match="must be a name or a dict"):
        sp.resolve_scenario(42)


def test_partitioner_registry():
    assert {"iid", "label", "dirichlet"} <= set(fd.PARTITIONERS)
    labels = np.repeat(np.arange(10), 30)
    parts = fd.partition("label", labels, 5, seed=1, classes_per_client=2)
    assert len(parts) == 5
    assert sorted(np.concatenate(parts).tolist()) == list(range(300))
    with pytest.raises(KeyError, match="unknown partitioner"):
        fd.get_partitioner("nope")

    def halves(labels, num_clients, *, seed=0):
        return [np.arange(len(labels) // 2),
                np.arange(len(labels) // 2, len(labels))]

    fd.register_partitioner("_test_halves", halves)
    try:
        assert len(fd.partition("_test_halves", labels, 2)) == 2
    finally:
        del fd.PARTITIONERS["_test_halves"]


def test_dirichlet_min_per_client_rebalance():
    labels = np.repeat(np.arange(10), 40)
    parts = fd.partition_dirichlet(labels, 16, alpha=0.05, seed=0,
                                   min_per_client=8)
    sizes = [len(p) for p in parts]
    assert min(sizes) >= 8
    assert sorted(np.concatenate(parts).tolist()) == list(range(400))
    # the raw draw at this skew genuinely starves clients (the rebalance
    # is doing real work)
    raw = fd.partition_dirichlet(labels, 16, alpha=0.05, seed=0)
    assert min(len(p) for p in raw) < 8
    with pytest.raises(ValueError, match="exceeds"):
        fd.partition_dirichlet(labels, 16, min_per_client=1000)


# ---------------------------------------------------------------------------
# The acceptance grid: 12 runs at M=64, f32 paper CNN
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def cnn_grid():
    from repro.configs.paper_cnn import CNNConfig

    M = 64
    task = CNNTask(iid=True, num_clients=M, train_n=16 * M, test_n=64,
                   batch_size=1, local_batches_per_step=2,
                   cnn_cfg=CNNConfig(conv1=2, conv2=4, fc=16))
    scenarios = ["paper_iid", "paper_noniid", "uplink_bound"]
    seeds = [0, 1, 2, 3]
    runs = sp.build_task_runs(task, scenarios, seeds, iterations=24)
    return task, runs


def test_cnn_grid_launch_bound(cnn_grid):
    """⌈R/sub⌉ · (#buckets + 2): the 12-run grid is ONE structure group
    and executes in ~#buckets launches, not 12x that."""
    task, runs = cnn_grid
    runner = sp.SweepRunner(runs)
    res = runner.run()
    assert res.stats["runs"] == 12
    assert res.stats["groups"] == 1
    n_buckets = max(len({int(b) for b in r.trace.s_buckets.tolist()})
                    for r in runs)
    assert runner.launches <= n_buckets + 2
    # per-run solo execution would pay >= R launches for the same work
    assert runner.launches <= len(runs)
    assert runner.variants() <= runner.launches + 1
    # sub-batching splits the group into ceil(R/sub) chunks
    runner2 = sp.SweepRunner(runs, sub_batch=5)
    res2 = runner2.run()
    assert runner2.launches <= int(np.ceil(12 / 5)) * (n_buckets + 2)
    for a, b in zip(res.params, res2.params):
        assert _maxdiff(a, b) <= 1e-6


def test_cnn_grid_parity_vs_solo_compiled(cnn_grid):
    """Per-run history AND final params ≤ 1e-5 vs 12 individual
    compiled_loop=True runs (eval curves on)."""
    task, runs = cnn_grid
    eval_flat = task.eval_flat_fn(runs[0].plane.engine)
    res = sp.SweepRunner(runs, eval_flat=eval_flat, eval_every=8).run()
    for i, r in enumerate(res.runs):
        solo = _solo(task, r, 24, eval_fn=task.eval_fn, eval_every=8)
        assert _maxdiff(r.params, solo.params) <= 1e-5, r.label
        assert r.history.times == solo.history.times, r.label
        assert r.history.iterations == solo.history.iterations, r.label
        np.testing.assert_allclose(r.history.series("accuracy"),
                                   solo.history.series("accuracy"),
                                   atol=1e-5, err_msg=r.label)


# ---------------------------------------------------------------------------
# bf16 toy grid: baseline broadcasts + FedOpt, run-batched
# ---------------------------------------------------------------------------
def _toy_runs(scenarios, seeds, *, D=97, M=4, iterations=16,
              dtype=jnp.bfloat16):
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=D), dtype)

    def batch_fn(cid, num_steps, seed_):
        r = np.random.default_rng((seed_ * 131 + cid) % (2 ** 31))
        return jnp.asarray(r.normal(size=(num_steps, D)), dtype)

    def step(flat, target):
        return (flat.astype(jnp.float32)
                - 0.25 * (flat.astype(jnp.float32)
                          - target.astype(jnp.float32))).astype(dtype)

    runs = []
    for entry in scenarios:
        sc = sp.resolve_scenario(entry)
        for seed in seeds:
            fleet = sc.make_fleet([60 + 20 * m for m in range(M)], seed)
            plane = ClientPlane(AggEngine(w0, storage_dtype=dtype),
                                fleet, step, batch_fn)
            trace = et.compile_afl_trace(
                fleet, algorithm=sc.algorithm, iterations=iterations,
                tau_u=sc.tau_u, tau_d=sc.tau_d, gamma=sc.gamma,
                mu_momentum=sc.mu_momentum,
                max_staleness=sc.max_staleness, seed=seed)
            runs.append(sp.SweepRun(sc, seed, plane, trace,
                                    plane.engine.flatten(w0),
                                    label=f"{sc.name}/s{seed}"))
    return w0, runs


@pytest.mark.parametrize("server_opt", [None, "momentum"])
def test_toy_bf16_grid_parity(server_opt):
    w0, runs = _toy_runs(["paper_iid", "baseline_cycle"], [0, 1])
    kw = {} if server_opt is None else {"server_opt": server_opt,
                                        "server_lr": 0.3}
    res = sp.SweepRunner(runs, **kw).run()
    # the two algorithms cannot share a group (retrain mode + broadcast
    # cuts differ), the two seeds of each can
    assert res.stats["groups"] == 2
    for r in res.runs:
        solo = _solo(w0, r, 16, **kw)
        assert _maxdiff(r.params, solo.params) <= 1e-5, r.label


def test_divergent_structures_fall_back_to_smaller_groups():
    """adaptive-K fleets draw different K_m per seed -> bucket structures
    diverge -> every run still executes (its own group), same math."""
    w0, runs = _toy_runs([{"name": "adaptive_k", "max_steps": 3}],
                         [0, 1, 2])
    res = sp.SweepRunner(runs).run()
    assert res.stats["groups"] > 1          # divergence actually happened
    for r in res.runs:
        solo = _solo(w0, r, 16)
        assert _maxdiff(r.params, solo.params) <= 1e-5, r.label


def test_fleet_seed_pins_timeline_across_seeds():
    w0, runs = _toy_runs([{"name": "adaptive_k", "fleet_seed": 5}],
                         [0, 1, 2])
    t0 = runs[0].trace
    for r in runs[1:]:
        np.testing.assert_array_equal(r.trace.cids, t0.cids)
        np.testing.assert_array_equal(r.trace.t_complete, t0.t_complete)
        assert not np.array_equal(r.trace.seeds, t0.seeds)
    # pinned adaptive fleets share structure -> ONE group (vs >1 above)
    res = sp.SweepRunner(runs).run()
    assert res.stats["groups"] == 1


def test_compile_trace_rejects_wrong_length_events():
    w0, runs = _toy_runs(["paper_iid"], [0])
    fleet = runs[0].plane.fleet
    ev = runs[0].trace.events
    with pytest.raises(ValueError, match="timeline has"):
        et.compile_afl_trace(fleet, algorithm="csmaafl", iterations=8,
                             tau_u=0.1, tau_d=0.1, events=ev)


def test_sweep_runner_input_validation():
    w0, runs = _toy_runs(["paper_iid"], [0, 1])
    with pytest.raises(ValueError, match="at least one run"):
        sp.SweepRunner([])
    # mismatched engine layout (different D) is refused up front
    _, other = _toy_runs(["paper_iid"], [0], D=31)
    with pytest.raises(ValueError, match="does not share"):
        sp.SweepRunner(runs + other)


def test_sweep_rejects_sharded_plane():
    task = CNNTask(iid=True, num_clients=4, train_n=200, test_n=50,
                   local_batches_per_step=2, batch_size=1)
    from repro.core.scheduler import make_fleet
    fleet = make_fleet(4, tau=1.0, hetero_a=4.0,
                       samples_per_client=task.num_samples(), seed=0)
    plane = task.client_plane(fleet, sharded=True)
    trace = et.compile_afl_trace(fleet, algorithm="csmaafl", iterations=4,
                                 tau_u=0.1, tau_d=0.1)
    run = sp.SweepRun(sp.get_scenario("paper_iid"), 0, plane, trace,
                      plane.engine.flatten(task.init_params()))
    with pytest.raises(NotImplementedError, match="single device"):
        sp.SweepRunner([run])


# ---------------------------------------------------------------------------
# Run-batched primitives == their single-run twins
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blend_runs_expr_matches_blend_row_expr(dtype):
    rng = np.random.default_rng(3)
    eng = AggEngine(jnp.zeros(53, dtype), storage_dtype=dtype)
    gs = jnp.asarray(rng.normal(size=(5, 53)), dtype)
    rows = jnp.asarray(rng.normal(size=(5, 53)), dtype)
    coefs = jnp.asarray(rng.uniform(0, 1, size=(5, 2)), jnp.float32)
    batched = eng.blend_runs_expr(gs, rows, coefs)
    for k in range(5):
        one = eng.blend_row_expr(gs[k], rows[k], coefs[k])
        assert _maxdiff(batched[k], one) == 0.0
    d_b = eng.delta_runs_expr(gs, rows, coefs[:, 1])
    for k in range(5):
        d1 = eng.delta_row_expr(gs[k], rows[k], coefs[k, 1])
        assert _maxdiff(d_b[k], d1) == 0.0


def test_train_all_runs_matches_per_run_train_all():
    w0, runs = _toy_runs(["paper_iid"], [0, 1, 2], dtype=jnp.float32)
    plane = runs[0].plane
    gs = jnp.stack([jnp.asarray(r.g0_flat) * (1 + 0.1 * k)
                    for k, r in enumerate(runs)])
    staged = [r.plane._stage_fleet(r.seed * 100003) for r in runs]
    batches = jax.tree.map(lambda *xs: np.stack(xs),
                           *[s[0] for s in staged])
    valid = np.stack([s[1] for s in staged])
    stacked = plane.train_all_runs(gs, batches, valid)
    for k, (r, s) in enumerate(zip(runs, staged)):
        one = plane._fleet_rows(gs[k], s[0], s[1])
        assert _maxdiff(stacked[k], one) <= 1e-6


def test_run_sweep_convenience_and_scenario_clients():
    task = CNNTask(iid=True, num_clients=6, train_n=360, test_n=60,
                   local_batches_per_step=2, batch_size=1)
    res = sp.run_sweep(task, ["paper_iid",
                              {"name": "dirichlet_skew",
                               "partition_kw": {"alpha": 0.5,
                                                "min_per_client": 4}}],
                       [0, 1], iterations=10, eval_every=5)
    assert len(res.runs) == 4
    for r in res.runs:
        # history: t=0 point + one per eval cut
        assert r.history.iterations[0] == 0
        assert r.history.iterations[-1] == 10
        assert all(k in m for m in r.history.metrics
                   for k in ("accuracy",))
    # dirichlet runs actually used a different partition than iid runs
    iid, diri = res.runs[0], res.runs[2]
    assert [c.num_samples for c in iid.plane.fleet] != \
        [c.num_samples for c in diri.plane.fleet]
