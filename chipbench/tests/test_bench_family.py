"""A model family the benchmark has never seen — softmax regression on
random features, written here into a directory of its own — runs through
the shared replay, the check and the per-layer readers with no file of
``chipbench/`` edited: a sound answer reads every compared number as 0,
a planted half batch fails the committed ``model_gap`` limits, and
``mfu`` and ``wagg_roofline`` take the family's own counts.  Only the
reference side and the counts are written: the program has no such
task."""
import json
import os
import textwrap

import pytest

from chipbench import check, flops, spec
from chipbench.drivers import Answer
from chipbench.reference import Reference
from chipbench.trace import WAGG_KERNEL, Events, Trace, TraceContext, \
    _assign_modules

STUB = textwrap.dedent('''
    """Softmax regression on Gaussian class clusters."""
    import numpy as np


    def dataset(config, seed):
        m, t = config["model"], config["task"]
        rng = np.random.default_rng(seed)
        y = rng.integers(0, m["classes"], t["train_n"]).astype(np.int32)
        centers = rng.normal(0.0, 1.0, (m["classes"], m["features"]))
        x = centers[y] + rng.normal(0.0, 1.0, (t["train_n"], m["features"]))
        return {"x": x.astype(np.float32), "y": y}, y


    def local_steps(config, k):
        return k * config["task"]["local_batches_per_step"]


    def batches(config, shard, cid, k, seed):
        rng = np.random.default_rng(seed * 7919 + cid)
        shape = (local_steps(config, k), config["task"]["batch_size"])
        return shard[rng.integers(0, len(shard), shape)].astype(np.int32)


    def init_params(model, seed):
        import jax
        import jax.numpy as jnp

        w = jax.random.normal(jax.random.PRNGKey(seed),
                              (model["features"], model["classes"]))
        return {"linear": {"w": 0.1 * w,
                           "b": jnp.zeros((model["classes"],), jnp.float32)}}


    def loss(params, batch, data):
        import jax
        import jax.numpy as jnp

        x, y = data["x"][batch], data["y"][batch]
        p = params["linear"]
        logp = jax.nn.log_softmax(x @ p["w"] + p["b"], axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


    def param_count(model):
        return (model["features"] + 1) * model["classes"]


    def forward_flops_per_sample(model):
        return 2 * model["features"] * model["classes"]
''')

CONFIG = {"family": "softmax_stub",
          "model": {"features": 12, "classes": 4},
          "task": {"train_n": 480, "num_clients": 6, "batch_size": 4,
                   "local_batches_per_step": 2, "lr": 0.5},
          "precision": "highest"}
FLEET = {"tau": 1.0, "hetero_a": 4.0, "adaptive": True, "min_steps": 1,
         "max_steps": 8, "base_local_steps": 1, "seed": 0}
AFL_RUN = {"iterations": 24, "tau_u": 0.1, "tau_d": 0.1, "gamma": 0.4,
           "mu_momentum": 0.9}
DATA_SEED, SEED = 2 ** 31 + 3, 2 ** 31 + 11


def _limits(workload):
    with open(os.path.join(spec.HERE, "limits", workload + ".json")) as f:
        return json.load(f)["limits"]


@pytest.fixture
def stub(monkeypatch, tmp_path):
    (tmp_path / "softmax_stub.py").write_text(STUB)
    monkeypatch.setattr(spec, "FAMILIES", str(tmp_path))
    return spec.Cell("stub", 1, CONFIG, {}, {}, [], [])


def _answer(ref, kind):
    """A finished run as the program would hand it in, made by the
    shared replay itself."""
    fam = ref.family
    w0 = fam.init_params(CONFIG["model"], SEED)
    shards = ref.shards("iid", DATA_SEED)
    part = {"kind": "iid", "seed": DATA_SEED}
    if kind == "fedavg":
        run = ref.fedavg(seed=SEED, w0=w0, shards=shards, fleet=FLEET,
                         rounds=3)
        return Answer("fedavg", SEED, DATA_SEED, w0, run.params, part, FLEET,
                      {"rounds": 3})
    run = ref.afl(seed=SEED, w0=w0, shards=shards, fleet=FLEET,
                  marks=(8, 16), **AFL_RUN)
    last = int(run.cids[-1])
    row = ref.retrain(w=run.params, seed=SEED, shards=shards, fleet=FLEET,
                      cid=last, j=AFL_RUN["iterations"])
    return Answer("afl", SEED, DATA_SEED, w0, run.params, part, FLEET,
                  AFL_RUN, cids=run.cids, betas=run.betas, last_cid=last,
                  last_row=row, marks=run.marks)


@pytest.mark.parametrize("kind,workload", [("afl", "mnist-csmaafl"),
                                           ("fedavg", "mnist-fedavg")])
def test_stub_family_replays_and_is_checked(stub, kind, workload):
    ref = Reference(stub.family, stub.config, DATA_SEED)
    a = _answer(ref, kind)
    sound = check.replay(ref, a, "highest")
    got = check.numbers(check.program(a), sound, a)
    assert set(got) == set(_limits(workload))
    assert all(v == 0.0 for v in got.values()), got
    # the leaves are named by their path in the nested tree
    assert sorted(sound["params"]) == ["['linear']['b']", "['linear']['w']"]
    half = check.numbers(check.replay(ref, a, "highest", "half_batch"),
                         sound, a)
    assert half["model_gap"] > _limits(workload)["model_gap"], half


def test_stub_family_counts_feed_the_readers(stub):
    ms = 1e6        # ns
    mods = [("jit_mac_rows(13)", 0.0, 2 * ms)]
    ops = [(WAGG_KERNEL, 0.5 * ms, 1 * ms)]
    ops_e, mods_e = Events.of(ops), Events.of(mods)
    trace = Trace(ops_e, _assign_modules(ops_e, mods_e), mods_e,
                  Events.of([("bench.run", 0.0, 4 * ms)]), 1)
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = TraceContext(trace, cell=stub, stats={"samples": 5000},
                       peaks=peaks, flops=flops)
    # 2·12·4 FLOPs a sample forward; (12 + 1)·4 parameters; 6 clients
    assert spec.metric_reader("mfu")(ctx) == pytest.approx(
        100 * 3 * 96 * 5000 / (4e-3 * 197e12))
    assert spec.metric_reader("wagg_roofline")(ctx) == pytest.approx(
        100 * (6 + 2) * 52 * 4 / 819e9 / 1e-3)
