"""The traffic generator and the harness's look-up by name, on the CPU."""
import collections
import importlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import arrivals, harness

HERE = Path(__file__).resolve().parent
INGEST = json.loads((HERE / "traffic" / "ingest.json").read_text())
SEEDS = (2**31 + 5, 2**33 + 9)


def _lengths(reqs):
    return [r.params["length"] for r in reqs if "length" in r.params]


def test_spread_gives_every_seed_the_same_sizes_in_another_order():
    a, b = (arrivals.requests(INGEST, 10.0, s) for s in SEEDS)
    la, lb = _lengths(a), _lengths(b)
    assert sorted(la) == sorted(lb)
    assert la != lb
    assert len(set(la)) == len(la) == 240
    lo, hi = INGEST["mix"][1]["spread"]["length"]
    assert min(la) == lo and max(la) == hi


def test_each_kind_gets_its_share_and_cycles_its_ranks():
    reqs = arrivals.requests(INGEST, 10.0, SEEDS[0])
    assert len(reqs) == 1440
    assert all(0 <= r.due <= 10.0 for r in reqs)
    assert [r.due for r in reqs] == sorted(r.due for r in reqs)
    kinds = collections.Counter(r.params["payload"] for r in reqs)
    assert kinds == {"dense_full": 240, "dense_flat": 240, "tt": 480,
                     "cp": 480}
    tt = [r.params["rank"] for r in reqs if r.params["payload"] == "tt"]
    assert tt[:6] == [2, 3, 4, 2, 3, 4]


def test_picks_are_drawn_by_popularity():
    mix = json.loads((HERE / "traffic" / "query.json").read_text())
    reqs = arrivals.requests(mix, 1.0, SEEDS[0], n_items=10000)
    items = collections.Counter(r.params["item"] for r in reqs)
    assert len(reqs) == 256 and max(items.values()) > 10
    assert all(r.params["top_m"] == 10 for r in reqs)


@pytest.mark.parametrize("edit", [
    lambda m: m.update(rate_per_s=1.0),
    lambda m: m["mix"][0].update(lengths=[1]),
    lambda m: m["arrivals"].update(burst=2),
    lambda m: m.pop("system"),
    lambda m: m["mix"][1]["spread"].update(length=[9, 3]),
], ids=["traffic-key", "entry-key", "process-parameter", "no-system",
        "empty-spread"])
def test_a_traffic_file_outside_the_schema_is_refused(edit):
    mix = json.loads(json.dumps(INGEST))
    edit(mix)
    with pytest.raises((ValueError, KeyError)):
        arrivals.requests(mix, 1.0, SEEDS[0])


def test_every_cell_finds_its_parts_by_name():
    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    for wl in bench["workloads"]:
        cell = harness.load_cell(wl["name"])
        mix = cell["traffic"]
        arrivals.validate(mix)
        assert hasattr(harness.system(mix), "build")
        if "arrivals" in mix:
            assert hasattr(arrivals.process(mix["arrivals"]["process"]),
                           "times")
        assert cell["limits"]
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert callable(harness.reader(m["name"]))
    for name in ("arrivals", "harness", "readers", "tracered", "work"):
        importlib.import_module(f"bench.{name}")


def test_readers_of_the_window():
    from bench import readers
    ctx = {"window": {"attempted": 4, "window_s": 2.0,
                      "latency_s": np.linspace(0.0, 1.0, 101)},
           "setup_s": 3.5}
    assert readers.per_call_ms(ctx) == 500.0
    assert readers.p95_ms(ctx) == pytest.approx(950.0)
    assert harness.reader("setup_s")(ctx) == 3.5
    assert readers.p95_ms({"window": {"latency_s": np.array([])}}) is None
