"""granite-4.0-h-micro as a LoRA client (``models/lora.py``,
``core/tasks.LoRALMTask``) against the benchmark family's plain
reference (``chipbench/families/granite_hybrid.py``), at tiny widths with
the full 10-layer pattern (9 Mamba-2 : 1 attention), on the CPU."""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lora
from repro.models import transformer as tmod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.families import granite_hybrid as fam  # noqa: E402

SEED = 2 ** 31 + 5


def tiny_model():
    """The configuration's model block at tiny widths: every kind of
    layer in its published order, two SSD chunks a sequence."""
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro-lora.json")) as f:
        model = json.load(f)["model"]
    model.update(hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, shared_intermediate_size=96,
                 vocab_size=512, mamba_d_state=16, mamba_d_head=16,
                 mamba_n_heads=8, mamba_chunk_size=16, seq_len=32,
                 lora={"rank": 4, "alpha": 8})
    return model


def tiny_config():
    return {"model": tiny_model(),
            "task": {"num_clients": 4, "batch_size": 2,
                     "local_batches_per_step": 1, "lr": 2.0,
                     "train_seqs": 16, "eval_seqs": 4, "num_topics": 4}}


@pytest.fixture(scope="module")
def pair():
    config = tiny_config()
    task = fam.task(config, True, SEED)
    data, _keys = fam.dataset(config, SEED)
    return config, task, data


def test_pattern_and_counts():
    """The published period, and the row and base sizes of the cell."""
    full = get_config("granite-4.0-h-micro")
    assert full.blocks[5] == full.blocks[15] == "attn_global"
    assert full.blocks.count("mamba") == 36
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "granite-4.0-h-micro-lora.json")) as f:
        config = json.load(f)
    model = config["model"]
    assert fam.param_count(model) == config["param_count"] == 7_205_888
    assert fam.base_param_count(model) == config["base_param_count"]
    cfg = fam.model_config(model)
    assert cfg.param_count == config["base_param_count"] == 951_991_232
    assert fam.param_count(model) % (8 * 128) == 0


def test_base_and_adapters_drawn_alike(pair):
    """The program's frozen base and adapters are the reference's draws,
    weight by weight."""
    config, task, data = pair
    ref = data["base"]
    np.testing.assert_array_equal(task.base["embed"], ref["embed"])
    names = {"mixer": {"in_proj": "in_proj", "conv_w": "conv_w",
                       "conv_b": "conv_b", "A_log": "A_log",
                       "dt_bias": "dt_bias", "D": "D",
                       "out_proj": "out_proj"},
             "attn": {k: k for k in ("wq", "wk", "wv", "wo")},
             "mlp": {"w_in": "w_in", "w_out": "w_out"}}
    for mine, theirs in zip(task.base["stack"]["rem"], ref["layers"]):
        for sub, pairs in names.items():
            for a, b in pairs.items():
                if sub in mine and a in mine[sub]:
                    np.testing.assert_array_equal(
                        np.asarray(mine[sub][a]).reshape(theirs[b].shape),
                        theirs[b])
    ad_prog = task.init_params(11)
    ad_ref = fam.init_params(config["model"], 11)
    assert jax.tree.structure(ad_prog) == jax.tree.structure(ad_ref)
    for x, y in zip(jax.tree.leaves(ad_prog), jax.tree.leaves(ad_ref)):
        np.testing.assert_array_equal(x, y)


def _trained_adapters(config, seed):
    """Adapters with B away from zero, so that every leaf has a
    gradient."""
    leaves, tree = jax.tree.flatten(fam.init_params(config["model"], seed))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten(x + 0.05 * jax.random.normal(k, x.shape)
                          for x, k in zip(leaves, keys))


def test_loss_and_gradients_match_reference(pair):
    """Tolerances: both sides are float32 on the CPU over the same
    weights, data and adapters, and differ only in the order of their
    sums (the SSD's chunk states by a scan here, by a segment-sum matrix
    there; attention by repeated KV heads here, grouped there): 1e-5 of
    the loss and 1e-4 of each gradient's norm, well under a bfloat16
    rounding (about 4e-3) in any one product."""
    config, task, data = pair
    ad = _trained_adapters(config, 3)
    rows = np.asarray([1, 6], np.int32)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda a: task._loss(task.base, task.tokens, a, rows))(ad)
        lr, gr = jax.value_and_grad(fam.loss)(ad, rows, data)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for x, y in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        err = float(jnp.linalg.norm(x - y))
        assert err <= 1e-4 * float(jnp.linalg.norm(y)) + 1e-12


def test_zero_b_is_the_base(pair):
    """With B = 0 the adapted model's logits are the base model's."""
    _config, task, _data = pair
    ad = task.init_params(5)
    toks = task.tokens[:2, :-1]
    base, _ = tmod.forward(task.base, task.cfg, {"tokens": toks})
    adapted, _ = tmod.forward(lora.attach(task.base, ad, task.scale),
                              task.cfg, {"tokens": toks})
    np.testing.assert_array_equal(np.asarray(base), np.asarray(adapted))


def test_batches_drawn_from_cid_and_seed(pair):
    """A client's minibatches depend on (cid, seed) alone, not on the
    calls made before, and are the reference's rows."""
    config, task, _data = pair
    first = task._batch_rows(2, 3, 77)
    for c in range(4):
        task._batch_rows(c, 2, 5)
    np.testing.assert_array_equal(task._batch_rows(2, 3, 77), first)
    shard = task.clients[2].indices
    np.testing.assert_array_equal(
        fam.batches(config, shard, 2, 3, 77), first)


def test_base_is_an_argument_of_the_plane_programs(pair):
    """The frozen base and the corpus enter the plane's programs as
    arguments: no constant in their lowered text has the shape of a
    base weight."""
    _config, task, _data = pair
    from repro.core.scheduler import make_fleet

    fleet = make_fleet(4, tau=1.0, hetero_a=4.0,
                       samples_per_client=task.num_samples(), seed=0,
                       adaptive=False)
    plane = task.client_plane(fleet)
    g = plane.engine.flatten(task.init_params(0))
    batches, valid = plane._stage_fleet(1)
    lowered = plane._train_all.lower(
        plane._new_rows(4), g, batches, valid, plane._lstep)
    text = lowered.as_text()
    n_args = len(jax.tree.leaves(lowered.args_info))
    assert n_args >= len(jax.tree.leaves(task.base)) + 1
    for leaf in jax.tree.leaves(task.base) + [task.tokens]:
        shape = "x".join(str(s) for s in leaf.shape)
        for line in text.splitlines():
            if "stablehlo.constant" in line:
                assert f"tensor<{shape}x" not in line, line
    one = plane._train_row.lower(
        jnp.zeros((4, plane.engine.n)), g, jnp.int32(0),
        jax.tree.map(lambda x: x[0], batches), valid[0], plane._lstep)
    assert len(jax.tree.leaves(one.args_info)) >= \
        len(jax.tree.leaves(task.base)) + 1
