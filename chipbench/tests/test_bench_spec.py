"""Every cell of BENCHMARK.json finds its pieces by name, and the
measurement path refuses to run without a TPU."""
import json
import os
import re

import pytest

from chipbench import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(workload):
    cell = spec.load_cell(workload, BENCH)
    assert cell.config["name"] == [w for w in BENCH["workloads"]
                                   if w["name"] == workload][0]["config"]
    assert callable(spec.entry_driver(cell.traffic["entry"]))
    assert cell.limits and all(v >= 0 for v in cell.limits.values())
    assert cell.per_layer, "every cell reports a per-layer metric"
    names = {m["name"] for m in cell.end_to_end}
    assert {"uploads_per_s", "setup_s"} <= names
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_size(cfg):
    with open(os.path.join(spec.ROOT, cfg["file"])) as f:
        body = json.load(f)
    assert body["name"] == cfg["name"] and body["reduced"] == cfg["reduced"]
    assert spec.family(body["family"]).param_count(body["model"]) == \
        body["param_count"]


# what every family gives (``families/__init__.py``)
CONTRACT = ("dataset", "local_steps", "batches", "init_params", "loss",
            "param_count", "forward_flops_per_sample", "task",
            "samples_per_iteration")


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_family_gives_the_contract(cfg):
    with open(os.path.join(spec.ROOT, cfg["file"])) as f:
        fam = spec.family(json.load(f)["family"])
    assert all(callable(getattr(fam, name, None)) for name in CONTRACT)


@pytest.mark.parametrize("name", [None, "no_such_family"])
def test_unknown_or_missing_family_is_refused(name):
    with pytest.raises(KeyError, match="paper_cnn"):
        spec.family(name)
    cell = spec.Cell("t", 1, {} if name is None else {"family": name},
                     {}, {}, [], [])
    with pytest.raises(KeyError, match="paper_cnn"):
        _ = cell.family


def test_names_units_and_bounds_keep_to_the_contract():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200


def test_unknown_entry_is_refused():
    with pytest.raises(KeyError):
        spec.entry_driver("no_such_entry")


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_measurement_path_fails_without_a_tpu(capsys):
    import jax

    from chipbench import run

    assert jax.devices()[0].platform != "tpu"
    rc = run.main(["--workload", BENCH["workloads"][0]["name"],
                   "--seed", "3", "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err and out.out.strip() == ""
