"""Run by hand, not part of tier-1 (like its neighbours):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

`setup_parts.py` and its six readers on a registry filled by hand, against
sums made by hand; on an empty registry; and the six entries of
BENCHMARK.json against the files beside this directory.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [ROOT, BENCH]

import run           # noqa: E402
import setup_parts   # noqa: E402
from singa_tpu import introspect, observe   # noqa: E402

READERS = tuple(n + ".train" for n in setup_parts.NAMES)

# span path -> the seconds the span was open (gross, as the histogram
# holds them); a first step's fetch holds the first execution
SPANS = {
    "model.create": 0.5,
    "model.init": 9.0,
    "opt.setup": 2.0,
    "model.build": 1.5,
    "model.build/opt.setup": 0.25,
    "introspect.build": 21.0,
    "introspect.build/trace": 4.0,
    "introspect.build/lower": 2.0,
    "introspect.build/compile": 14.5,
    "model.step": 3.25,
    "model.step/introspect.first_dispatch": 3.0,
    "tensor.fetch": 7.0,
}
# (source, where) -> [seconds of each program]
COMPILES = {
    ("backend", "model.init"): [0.5, 0.25],
    ("cache", "model.init"): [0.125],
    ("cache", "compile"): [14.0],
    ("backend", "none"): [20.0],
    ("cache", "none"): [1.5, 0.5],
}


@pytest.fixture
def registry():
    reg = observe.get_registry()
    reg.reset()
    introspect.reset()
    yield reg
    reg.reset()


def fill(reg):
    spans = reg.histogram("singa_span_seconds")
    for path, s in SPANS.items():
        spans.observe(s, span=path)
    xla = reg.histogram("singa_xla_compile_seconds")
    for (source, where), each in COMPILES.items():
        for s in each:
            xla.observe(s, source=source, where=where)


def record(tmp_path, setup_s=50.0):
    return {"values": {"setup_s": setup_s},
            "hlo_dir": str(tmp_path / "cell" / "hlo")}


def test_six_values_of_a_hand_made_registry(registry, tmp_path):
    fill(registry)
    rec = record(tmp_path)
    got = {n: run.load_module("layer_metrics", n).read(rec, None)
           for n in READERS}
    # create + init + Model.compile's opt.setup; model.build's own
    # opt.setup is out of both, and out of model.build (net)
    assert got["setup_init_s.train"] == 0.5 + 9.0 + 2.0
    assert got["setup_trace_s.train"] == (1.5 - 0.25) + 4.0 + 2.0
    assert got["setup_compile_s.train"] == 14.5 + 3.0
    assert got["setup_backend_compiles.train"] == 3
    assert got["setup_outside_compile_s.train"] == 20.0 + 1.5 + 0.5
    assert got["setup_attributed_share.train"] == pytest.approx(
        100 * (11.5 + 7.25 + 17.5) / 50.0)
    with open(tmp_path / "cell" / "setup_parts.json") as f:
        left = json.load(f)
    assert left["values"] == {n[:-len(".train")]: v for n, v in got.items()}
    assert left["report"]["spans"]["model.step"]["seconds"] == 0.25
    assert left["report"]["compiles"]["none"]["cache"]["count"] == 2
    # read once a record: a registry that moves on does not move the six
    registry.reset()
    assert run.load_module("layer_metrics", READERS[0]).read(rec, None) \
        == 11.5


def test_an_empty_registry_reads_none_six_times(registry, tmp_path):
    rec = record(tmp_path)
    assert [run.load_module("layer_metrics", n).read(rec, None)
            for n in READERS] == [None] * 6
    assert not os.path.exists(tmp_path / "cell" / "setup_parts.json")


def test_a_program_without_the_report_reads_none(registry, tmp_path,
                                                 monkeypatch):
    fill(registry)
    monkeypatch.delattr(introspect, "setup_report")   # the parent's
    assert setup_parts.parts(record(tmp_path)) is None


def test_the_six_entries_name_files_and_list_the_five_cells():
    bench = run.load_json("BENCHMARK.json")
    cells = [w["name"] for w in bench["workloads"]]
    assert len(cells) == 5
    mine = [m for m in bench["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in mine] == list(READERS)
    assert bench["per_layer"][-6:] == mine        # appended, in order
    for m in mine:
        assert os.path.exists(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".py"))
        assert m["workloads"] == cells
        assert (m["moves"], m["layer"], m["source"]) == (
            "setup_s", "entry points", "program_counter")
    assert any(e["name"] == "setup_s" for e in bench["end_to_end"])
