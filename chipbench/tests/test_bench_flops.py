"""Operation and byte counts against a hand count of the paper CNN."""
import json
import os

import pytest

from chipbench import flops, spec

CNN = spec.family("paper_cnn")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MNIST = {"image_size": 28, "channels": 1, "conv1": 32, "conv2": 64,
         "kernel": 5, "fc": 512, "num_classes": 10}
# the paper's Fashion-MNIST CNN, fc 1024 (no cell runs it yet)
FASHION = dict(MNIST, fc=1024)


@pytest.mark.parametrize("model,n,fwd", [
    # conv1 5*5*1*32+32, conv2 5*5*32*64+64, fc 3136*fc+fc, head fc*10+10
    (MNIST, 832 + 51264 + 1606144 + 5130,
     # 2 x (28*28*32*25 + 14*14*64*800 + 3136*512 + 512*10) MACs
     2 * (627200 + 10035200 + 1605632 + 5120)),
    (FASHION, 832 + 51264 + 3212288 + 10250,
     2 * (627200 + 10035200 + 3211264 + 10240)),
], ids=["mnist", "fashion"])
def test_counts_match_hand_count(model, n, fwd):
    assert CNN.param_count(model) == n
    assert CNN.forward_flops_per_sample(model) == fwd
    assert flops.train_flops(CNN, model, 10) == 30 * fwd


def test_param_counts_are_the_published_sizes():
    assert CNN.param_count(MNIST) == 1663370
    assert CNN.param_count(FASHION) == 3274634
    with open(os.path.join(HERE, "configs", "paper-cnn-mnist.json")) as f:
        cfg = json.load(f)
    assert cfg["model"] == MNIST and cfg["param_count"] == 1663370


@pytest.mark.parametrize("clients,runs", [(1, 1), (1, 4), (100, 1)])
def test_wagg_bytes_reads_clients_and_global_writes_global(clients, runs):
    n = 1663370
    assert flops.wagg_bytes(n, clients, runs) == (clients + 2) * n * 4 * runs
