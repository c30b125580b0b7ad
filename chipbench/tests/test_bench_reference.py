"""The plain reference rebuilds the program's inputs bit for bit, and its
replay agrees with the program's compiled run at a small size."""
import numpy as np
import pytest

from chipbench import check, spec
from chipbench import reference as R

CNN = spec.family("paper_cnn")


@pytest.mark.parametrize("variant", ["digits", "fashion"])
def test_training_set_matches_program_generator(variant):
    from repro.data.mnist_like import make_dataset

    x, y = CNN.training_set(variant, 700, 90, 2 ** 31 + 5)
    ds = make_dataset(variant, train_n=700, test_n=90, seed=2 ** 31 + 5)
    assert np.array_equal(x, ds.train_x) and np.array_equal(y, ds.train_y)


@pytest.mark.parametrize("kind,kw", [("iid", {}),
                                     ("label", {"classes_per_client": 2})])
def test_partition_and_batches_match_program(kind, kw):
    from repro.data import federated as fd

    y = np.random.default_rng(0).integers(0, 10, 1200).astype(np.int32)
    mine = R.partition(kind, y, 12, 77, **kw)
    theirs = fd.partition(kind, y, 12, seed=77, **kw)
    assert all(np.array_equal(a, b) for a, b in zip(mine, theirs))
    cl = fd.make_clients(np.zeros((1200, 1)), y, theirs)
    for cid in (0, 5, 11):
        want = cl[cid].indices[cl[cid].batch_indices(5, 40, 123457)]
        assert np.array_equal(
            CNN.batch_indices(mine[cid], cid, 5, 40, 123457), want)


def test_fleet_schedule_and_betas_match_program():
    from repro.core.event_trace import compile_afl_trace
    from repro.core.scheduler import make_fleet

    taus, ks = R.fleet_steps(100, tau=1.0, hetero_a=4.0, seed=0,
                             adaptive=True, min_steps=1, max_steps=8,
                             base_local_steps=1)
    fl = make_fleet(100, tau=1.0, hetero_a=4.0,
                    samples_per_client=[600] * 100, seed=0)
    assert ks == [c.local_steps for c in fl]
    assert np.array_equal(taus, [c.tau_compute for c in fl])
    ev = R.schedule(taus, ks, tau_u=0.1, tau_d=0.1, iterations=1024)
    tr = compile_afl_trace(fl, algorithm="csmaafl", iterations=1024,
                           tau_u=0.1, tau_d=0.1, gamma=0.4,
                           mu_momentum=0.9, seed=3)
    assert np.array_equal([e[0] for e in ev], tr.cids)
    betas = R.csmaafl_betas(ev, gamma=0.4, mu_momentum=0.9)
    # the program replays the EMA in closed form, the reference as the
    # recurrence: they agree to float64 rounding
    assert np.max(np.abs(betas - tr.betas)) < 1e-12


def test_init_params_match_program_init():
    import jax

    from repro.configs.paper_cnn import CNNConfig
    from repro.models import cnn

    model = {"image_size": 28, "channels": 1, "conv1": 4, "conv2": 8,
             "kernel": 5, "fc": 16, "num_classes": 10}
    mine = CNN.init_params(model, 99)
    theirs = cnn.init_params(CNNConfig(conv1=4, conv2=8, fc=16),
                             jax.random.PRNGKey(99))
    assert all(np.array_equal(mine[k], theirs[k]) for k in theirs)


def test_gap_reads_one_for_an_unmoved_model():
    w0 = {"a": np.zeros(4), "b": np.zeros(3)}
    moved = {"a": np.ones(4), "b": np.ones(3)}
    assert check.gap(w0, moved, w0) == 1.0
    assert check.gap(moved, moved, w0) == 0.0
    bad = {"a": np.full(4, np.nan), "b": np.ones(3)}
    assert check.gap(bad, moved, w0) == float("inf")


def test_diff_gap_sees_an_error_the_norms_miss():
    w0 = {"a": np.zeros(2), "b": np.zeros(2)}
    ref = {"a": np.array([1.0, 0.0]), "b": np.array([0.0, 1.0])}
    turned = {"a": np.array([0.0, 1.0]), "b": np.array([0.0, 1.0])}
    assert check.gap(turned, ref, w0) == 0.0
    assert check.diff_gap(turned, ref, w0) == np.sqrt(2.0)
    assert check.diff_gap(ref, ref, w0) == 0.0
