"""What ``BENCHMARK.json`` says twice, it says alike: each per-layer metric is
one entry there and one file under ``layer_metrics/`` (which adds the reader),
each configuration one entry and one file."""

import json
import os

from benchmark import spec


def _bench():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_per_layer_entries_equal_their_files():
    entries = {m["name"]: m for m in _bench()["per_layer"]}
    folder = os.path.join(spec.HERE, "layer_metrics")
    files = {}
    for fname in os.listdir(folder):
        with open(os.path.join(folder, fname)) as f:
            metric = json.load(f)
        assert fname == metric["name"] + ".json"
        files[metric["name"]] = {k: metric[k] for k in metric
                                 if k in entries.get(metric["name"], ())}
        assert os.path.exists(os.path.join(
            spec.HERE, "readers", metric["reader"] + ".py"))
    assert files == entries


def test_config_entries_equal_their_files():
    for entry in _bench()["configs"]:
        with open(os.path.join(spec.ROOT, entry["file"])) as f:
            config = json.load(f)
        assert {k: config[k] for k in ("name", "source", "reduced")} == {
            k: entry[k] for k in ("name", "source", "reduced")}
        assert os.path.exists(os.path.join(
            spec.HERE, "reference", config["reference"] + ".py"))


def test_every_cell_loads_and_has_limits_for_its_mode():
    for row in _bench()["workloads"]:
        cell = spec.load_cell(row["name"])
        assert cell.config["limits"][cell.mode]
        assert cell.per_layer and len(cell.end_to_end) >= 2
