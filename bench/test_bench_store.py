"""The store's query cell, and its ingest traffic, at a tiny size on the
CPU, through the harness's own functions (kernels in interpret mode): each
comparison passes, and fails for the control and for each fault the cell
can have.

The ingest traffic has no cell in BENCHMARK.json yet (its ragged lengths
compile one program each in the program's padding, more than a first run
can warm); it is put together here from its files as a cell would be."""
import copy
import json
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.peaks import PEAKS
from repro import rp
from repro.serve import store as store_mod

SEED = 2**32 + 11
HERE = Path(__file__).resolve().parent
INGEST = {
    "end_to_end": [{"name": "ingest_p95_ms", "unit": "ms"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [{"name": n, "unit": u} for n, u in [
        ("kernel_roofline.ingest", "%"), ("tick_ms.ingest", "ms"),
        ("plan_builds.ingest", "count"), ("idle_share.ingest", "%")]],
}


def load(name):
    if name != "store1m-tt32.ingest":
        return harness.load_cell(name)
    cfg = json.loads((HERE / "configs" / "store1m-tt32.json").read_text())
    mix = json.loads((HERE / "traffic" / "ingest.json").read_text())
    return {"workload": {"name": name, "config": "store1m-tt32",
                         "traffic": "ingest", "chips": 1},
            "config": cfg, "traffic": mix,
            "limits": cfg["limits"],
            **copy.deepcopy(INGEST)}


def tiny_cell(name):
    cell = load(name)
    c = copy.deepcopy(cell["config"])
    c["store_items"] = 3000
    a = c["assumed"]
    a["projector"] = {"family": "tt", "k": 128, "rank": 2, "dims": [8, 8, 8]}
    a["serve"] = dict(a["serve"], max_batch=4)
    cell["config"] = c
    mix = copy.deepcopy(cell["traffic"])
    if mix["loop"] == "open":
        mix["arrivals"]["rate_per_s"] = min(mix["arrivals"]["rate_per_s"],
                                            60.0)
    for e in mix["mix"]:
        if "length" in e.get("spread", {}):
            e["spread"]["length"] = [385, 512]
        if "rank" in e.get("cycle", {}):
            e["cycle"]["rank"] = e["cycle"]["rank"][:2]
    cell["traffic"] = mix
    return cell


def run(cell, *, traced=False, **kw):
    with rp.force_pallas():
        return harness.measure(cell, SEED, 0.5, traced, time.perf_counter(),
                               jax.devices(),
                               chip_peaks=lambda _: PEAKS["TPU v5 lite"],
                               **kw)


@pytest.fixture(scope="module")
def ingest():
    return tiny_cell("store1m-tt32.ingest")


@pytest.fixture(scope="module")
def query():
    return tiny_cell("store1m-tt32.query")


def test_ingest_cell_is_correct_and_reports_its_metrics(ingest):
    out = run(ingest, traced=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 30 and out["failed"] == 0
    m = out["metrics"]
    assert m["plan_builds.ingest"]["value"] == 0
    assert m["tick_ms.ingest"]["value"] > 0
    assert "idle_share.ingest" in m


def test_query_cell_is_correct_and_reports_its_metrics(query):
    out = run(query)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"query_ms", "setup_s"}


@pytest.mark.parametrize("precision", ["high", "bf16"])
@pytest.mark.parametrize("name", ["store1m-tt32.ingest",
                                  "store1m-tt32.query"])
def test_control_at_lower_precision_is_not_correct(name, precision):
    assert not run(tiny_cell(name), control=precision)["correct"]


def _faulty_project_many(kind):
    real = rp.project_many

    def bad(op, inputs, **kw):
        ys = np.array(real(op, inputs, **kw))
        if kind == "altered":
            ys[0] *= 1.001
        else:
            ys[len(ys) // 2:] = 0.0
        return ys
    return bad


@pytest.mark.parametrize("kind", ["altered", "half-batch"])
def test_faults_in_ingest_are_not_correct(ingest, monkeypatch, kind):
    monkeypatch.setattr(rp, "project_many", _faulty_project_many(kind))
    assert not run(ingest)["correct"]


def _moved(ids):
    """The last id moved one place down the store."""
    ids[..., -1] = (ids[..., -1] + 1) % 3000


def _duplicated(ids):
    """The nearest id returned twice, in the second place too."""
    ids[..., 1] = ids[..., 0]


@pytest.mark.parametrize("alter,number", [(_moved, "query_err"),
                                          (_duplicated, "query_order")],
                         ids=["moved", "duplicated"])
def test_an_altered_query_answer_is_not_correct(query, monkeypatch, alter,
                                                number):
    real = store_mod.SketchStore.query

    def bad(self, q, top_m, **kw):
        res = real(self, q, top_m, **kw)
        ids = np.array(res.ids)
        alter(ids)
        return store_mod.QueryResult(ids, res.dist2, res.eps, res.delta)

    monkeypatch.setattr(store_mod.SketchStore, "query", bad)
    out = run(query)
    assert not out["correct"], out["checks"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]
