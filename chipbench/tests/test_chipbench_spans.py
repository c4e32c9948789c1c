"""The readers of the program's spans against a hand-made snapshot: each
returns its defined value, returns None where its spans are absent, and is
found through the harness's own lookup from its ``BENCHMARK.json`` entry."""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

from chipbench.run import _reader_path  # noqa: E402
from repro.telemetry import spans  # noqa: E402

BENCH = os.path.join(ROOT, "chipbench")
READERS = ("boundary_host_share", "rank_map_share", "runner_compile_s")

SNAPSHOT = {
    "spans": {"segment": {"count": 4, "s": 2.0},
              "segment.wait": {"count": 4, "s": 1.7},
              "preprocess.build": {"count": 1, "s": 16.0},
              "preprocess.rank_map": {"count": 1, "s": 6.0}},
    "compile": {"segment": {"count": 2, "s": 8.5},
                "": {"count": 30, "s": 3.0}},
}
EXPECTED = {"boundary_host_share": 100.0 * (1.0 - 1.7 / 2.0),
            "rank_map_share": 100.0 * 6.0 / 16.0,
            "runner_compile_s": 8.5}
# the entries each reader needs, dropped one at a time
NEEDS = {"boundary_host_share": [("spans", "segment"),
                                 ("spans", "segment.wait")],
         "rank_map_share": [("spans", "preprocess.build"),
                            ("spans", "preprocess.rank_map")],
         "runner_compile_s": [("compile", "segment")]}


def _reader(name):
    path = _reader_path(BENCH, name)
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _snapshot(monkeypatch, snap):
    monkeypatch.setattr(spans, "snapshot", lambda: snap)


@pytest.mark.parametrize("name", READERS)
def test_reader_value(monkeypatch, name):
    _snapshot(monkeypatch, SNAPSHOT)
    assert _reader(name).read(None, {}, {}, {}) == pytest.approx(
        EXPECTED[name])


@pytest.mark.parametrize("name,drop", [(n, d) for n in READERS
                                       for d in NEEDS[n]])
def test_reader_none_without_its_spans(monkeypatch, name, drop):
    snap = {k: dict(v) for k, v in SNAPSHOT.items()}
    del snap[drop[0]][drop[1]]
    _snapshot(monkeypatch, snap)
    assert _reader(name).read(None, {}, {}, {}) is None


def test_entries_resolve_to_readers():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"] for w in spec["workloads"]}
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in READERS:
        m = entries[name]
        assert m["source"] == "program_span"
        assert set(m["workloads"]) <= cells
        assert os.path.basename(_reader_path(BENCH, name)) == name + ".py"
        assert callable(_reader(name).read)
