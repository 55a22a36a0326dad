"""A pair recorded on one v5e chip after the model code got its device
scopes (PR 36): 0.068 s of the saturated Pythia cell's traced window
(five iterations: four of the decode-only shape, one that carried a
chunk) and the table that ``program_scopes()`` gave in that process.  The reader joins the two as a
run does; nothing here touches a device."""
import gzip
import json
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import trace
from benchmark.readers import trace_scope_share, trace_share
from deepspeed_tpu.observability import overlap

HERE = os.path.dirname(os.path.abspath(__file__))
PAIR = os.path.join(HERE, "data", "serve_scopes_v5e_1chip")
BOOKED = overlap.SCOPES + (overlap.UNNAMED, overlap.AMBIGUOUS)


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(PAIR + ".xplane.pb.gz") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(str(path), ("serve_step", "plan_submit"))


@pytest.fixture
def recorded_table(monkeypatch):
    with gzip.open(PAIR + ".scopes.json.gz", "rt") as f:
        table = {k: tuple(v) for k, v in json.load(f).items()}

    class Program:
        def program_scopes(self):
            return table
    monkeypatch.setattr(overlap, "get_overlap_profiler", Program)
    return table


def _obs(red):
    return {"trace": red, "diag": {}}


def test_scopes_unnamed_and_ambiguous_sum_to_busy(red, recorded_table):
    obs = _obs(red)
    shares = {s: trace_scope_share.read(obs, s) for s in BOOKED}
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.5)
    assert shares[overlap.UNNAMED] + shares[overlap.AMBIGUOUS] < 10.0
    # the dense serving step has these and nothing of an expert layer
    for scope in ("attn_proj", "attn_kernel", "pool_write", "mlp", "head"):
        assert shares[scope] > 1.0, scope
    for scope in ("router", "expert_layout", "experts", "shared_expert",
                  "loss", "optimizer", "zero_comm"):
        assert shares[scope] == 0.0, scope
    assert trace_scope_share.read(obs, "recompute") == 0.0
    assert obs["diag"]["scope_table_keys"] == len(recorded_table)
    assert obs["diag"]["scope_share"]["mlp"] == shares["mlp"]


def test_the_kernels_scope_holds_the_kernel(red, recorded_table):
    """``attn_kernel`` is the Pallas call and what feeds it: no less
    than the call's own share, timed from outside by its name."""
    pattern = harness.declaration("sat.paged_time_share")["args"]["pattern"]
    kernel = trace_share.read({"trace": red}, pattern)
    scope = trace_scope_share.read(_obs(red), "attn_kernel")
    assert 20.0 < kernel <= scope < kernel + 5.0


def test_every_event_meets_a_line_of_the_programs_text(red, recorded_table):
    keys = {overlap.scope_key(name) for name in red["op_s"]}
    assert None not in keys and keys <= set(recorded_table)


def test_a_program_without_the_accessor_reports_nothing(red, monkeypatch):
    monkeypatch.setattr(overlap, "get_overlap_profiler", object)
    assert trace_scope_share.read(_obs(red), "mlp") is None
    assert trace_scope_share.read({"trace": {}, "diag": {}}, "mlp") is None


def test_declared_entries_name_a_scope_the_program_has():
    for m in harness.load_benchmark()["per_layer"]:
        decl = harness.declaration(m["name"])
        if decl["reader"] == "trace_scope_share":
            assert decl["args"]["scope"] in BOOKED + (
                trace_scope_share.RECOMPUTE,)
            assert m["source"] == "device_trace" and m["unit"] == "%"
