"""BENCHMARK.json against the files the harness finds by name."""
import importlib
import json
import os

import pytest

from benchmark import run as harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = harness.load_benchmark()


def test_every_metric_has_a_declaration_and_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        decl = harness.declaration(m["name"])
        reader = importlib.import_module("benchmark.readers." + decl["reader"])
        assert callable(reader.read)


def test_every_cell_has_its_files_and_reports_enough():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for cell in BENCH["workloads"]:
        cfg = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
        assert os.path.isfile(os.path.join(os.path.dirname(HERE),
                                           cfg["file"]))
        mix = json.load(open(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(HERE, "runners",
                                           mix["kind"] + ".py"))
        mine = [m["name"] for m in harness.metrics_of(BENCH, "end_to_end",
                                                      cell["name"])]
        assert "setup_s" in mine and len(mine) >= 2
        layer = harness.metrics_of(BENCH, "per_layer", cell["name"])
        assert layer
        for m in layer:
            assert m["moves"] in mine, (cell["name"], m["name"])
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])


def test_per_layer_has_room_and_no_declaration_is_an_orphan():
    """The file's limit is 128 entries; a declaration that no entry
    resolves to (``sat.x``, ``paced.x`` and ``train.x`` find ``x.json``)
    is a retired metric's that stayed behind."""
    assert len(BENCH["per_layer"]) <= 128
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    resolved = {os.path.basename(harness.declaration_path(n)) for n in names}
    assert set(os.listdir(os.path.join(HERE, "metrics"))) == resolved


def _builder_of(cfg_name):
    """The ``build`` of the runner that some cell of this configuration
    runs: a block's own, or ``lib/model.py``'s for the dense ones."""
    from benchmark.lib import model, traffic
    cell = next(w for w in BENCH["workloads"] if w["config"] == cfg_name)
    runner = importlib.import_module(
        "benchmark.runners." + traffic.load(cell["traffic"])["kind"])
    return getattr(runner, "build", model.build)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_published_sizes_match_what_the_program_builds(cfg):
    """Every ``build`` raises where the program's sizes differ from the
    configuration file's published ones; the family is the file's own."""
    from benchmark.lib import model
    config = model.load_config(cfg["file"])
    mc, ref = _builder_of(cfg["name"])(config)[:2]
    assert ref.get("family", config["family"]) == config["family"]
    assert mc.num_layers > 0 and mc.vocab_size == config["vocab_size"]
    if cfg["name"] == "pythia-1.4b":
        assert mc.num_params() == 1_414_647_808       # published
