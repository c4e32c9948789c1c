"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
one-chip phases pass end to end at a tiny size with the kernels in
interpret mode — the rehearsal that keeps the chip run from rotting."""
import importlib.util
import pathlib
import sys

import pytest

from repro import learner
from repro.data.networks import network_data

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses look it up there
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules["chip_smoke"]


def test_chip_smoke_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == "" and "needs 1 TPU chip" in err


def test_chip_smoke_one_chip_phases_tiny(chip_smoke, monkeypatch, tmp_path):
    # the streaming-pruned default engine only engages above AUTO_PRUNE_S
    monkeypatch.setattr(learner, "AUTO_PRUNE_S", 10)
    cell = chip_smoke.Cell(n=10, q=3, s=2, m=200, chains=2, window=4,
                           iters=100, serve_m=150, serve_iters=100)
    truth, data = network_data("synth", cell.m, cell.q, cell.seed,
                               n_synth=cell.n)
    chip_smoke.one_chip(cell, truth, data, str(tmp_path),
                        require_mosaic=False)
