"""A whole run of granite-h-lora-csmaafl, cut to a CPU size (tiny widths,
the full 10-layer pattern), comes out correct, and the faults a LoRA run
can have, planted in the program, turn ``correct`` false under the
committed limits."""
import time

import pytest

from chipbench import harness, spec
from chipbench.tests import faults

WORKLOAD = "granite-h-lora-csmaafl"


def tiny_cell():
    """The cell at tiny widths: every kind of layer in its published
    order, two SSD chunks a sequence, M and the run as the cell has
    them."""
    cell = spec.load_cell(WORKLOAD)
    cell.config["model"].update(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        shared_intermediate_size=96, vocab_size=512, mamba_d_state=16,
        mamba_d_head=16, mamba_n_heads=8, mamba_chunk_size=16, seq_len=32,
        lora={"rank": 4, "alpha": 8})
    cell.config["task"].update(train_seqs=32, num_topics=4)
    return cell


def run_tiny(seed: int = 2 ** 31 + 29):
    return harness.run_cell(tiny_cell(), seed, 0.2, False,
                            t_start=time.perf_counter(), log=lambda s: None)


def half_batch(monkeypatch):
    """Each minibatch's loss is the mean over its first half only."""
    from repro.models import transformer

    orig = transformer.loss_fn

    def loss_fn(params, cfg, batch, **kw):
        k = max(1, batch["labels"].shape[0] // 2)
        return orig(params, cfg, {n: v[:k] for n, v in batch.items()}, **kw)

    monkeypatch.setattr(transformer, "loss_fn", loss_fn)


def test_sound_run_is_correct():
    out = run_tiny()
    assert out.line["correct"] is True, out.line["checks"]
    assert out.info["window_compile_events"] == 0


@pytest.mark.parametrize("plant", [half_batch, faults.blend_coef],
                         ids=lambda f: f.__name__)
def test_fault_is_caught(monkeypatch, plant):
    plant(monkeypatch)
    out = run_tiny()
    assert out.line["correct"] is False, out.line["checks"]
