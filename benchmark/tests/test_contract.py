"""BENCHMARK.json against the files the harness finds by name."""
import importlib
import json
import os

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


def test_published_sizes_match_what_the_program_builds():
    from benchmark.lib import model
    for cfg in BENCH["configs"]:
        mc, ref = model.build(model.load_config(cfg["file"]))
        assert ref["family"] in ("gpt2", "neox")
    assert mc.num_params() == 1_414_647_808       # pythia-1.4b, published
