"""The traffic generator and the graph family: deterministic per seed, and
the family is the program's own graph, id for id."""
import collections
import glob
import itertools
import json
import os

import numpy as np
import pytest

from bench import generator
from repro.core import workloads as wl

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRAFFIC = sorted(glob.glob(os.path.join(BENCH, "traffic", "*.json")))


@pytest.mark.parametrize("sizes", [(1, 3, 1, 0), (2, 4, 2, 3), (5, 6, 3, 7)])
def test_arrow_family_is_the_programs_graph(sizes):
    got = generator.build_graph(ROOT, {"family": "arrow_lu", "blocks": sizes[0],
                                       "block_size": sizes[1], "border": sizes[2],
                                       "seed": sizes[3]})
    want = wl.arrow_lu_graph(*sizes[:3], seed=sizes[3])
    for k, v in got.items():
        np.testing.assert_array_equal(v, getattr(want, k))
        assert v.dtype == getattr(want, k).dtype


def test_every_configuration_names_a_family_that_builds():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for c in spec["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            family = json.load(f)["graph"]["family"]
        assert os.path.exists(os.path.join(BENCH, "families", f"{family}.py"))


@pytest.mark.parametrize("path", TRAFFIC, ids=os.path.basename)
def test_traffic_is_deterministic_per_seed(path):
    traffic = generator.load_traffic(path)
    base = generator.build_graph(ROOT, {"family": "arrow_lu", "blocks": 2,
                                        "block_size": 4, "border": 2, "seed": 3})
    v0 = base["initial_values"]
    scale = traffic.params["value_scale"]
    big = 2**33 + 12345
    assert _first(traffic, big, 6) == _first(traffic, big, 6)
    for i in range(3):
        a = generator.query_values(scale, v0, big, i)
        b = generator.query_values(scale, v0, big, i)
        np.testing.assert_array_equal(a, b)
        assert a.dtype == np.float32
        lo, hi = scale
        nz = v0 != 0
        ratio = a[nz] / v0[nz]
        assert (ratio >= lo * (1 - 1e-6)).all() and (ratio <= hi * (1 + 1e-6)).all()
        np.testing.assert_array_equal(a[~nz], 0)
        assert not np.array_equal(a, generator.query_values(scale, v0, big + 1, i))
        assert not np.array_equal(a, generator.query_values(scale, v0, big, i + 1))


def _first(traffic, seed, n):
    return list(itertools.islice(generator.schedule(traffic, seed), n))


def _traffic(tmp_path, **raw):
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"driver": "d", **raw}))
    return generator.load_traffic(str(p))


def test_closed_loop_sends_every_query_new(tmp_path):
    t = _traffic(tmp_path, arrivals={"kind": "closed"}, calls=[1])
    specs = _first(t, 2**33 + 1, 50)
    assert [(s.index, s.key, s.arrival_s) for s in specs] == [
        (i, i, None) for i in range(50)]
    assert t.params == {"calls": [1]}


@pytest.mark.parametrize("burst", [1, 4])
def test_poisson_arrivals_keep_their_rate_and_bursts(tmp_path, burst):
    t = _traffic(tmp_path, arrivals={"kind": "poisson", "rate_per_s": 20.0,
                                     "burst": burst})
    seed = 2**33 + 7
    specs = _first(t, seed, 4000)
    times = np.array([s.arrival_s for s in specs])
    assert times[0] == 0 and (np.diff(times) >= 0).all()
    assert 4000 / times[-1] == pytest.approx(20.0, rel=0.1)
    # A burst arrives at one instant; bursts apart.
    groups = times.reshape(-1, burst)
    assert (groups == groups[:, :1]).all()
    assert (np.diff(groups[:, 0]) > 0).all()
    assert _first(t, seed, 40) != _first(t, seed + 1, 40)


def test_repeats_follow_zipf_over_first_appearance(tmp_path):
    t = _traffic(tmp_path, arrivals={"kind": "closed"},
                 repeat={"share": 0.5, "zipf_s": 1.0})
    specs = _first(t, 2**33 + 3, 4000)
    repeats = [s for s in specs if s.key != s.index]
    assert len(repeats) / len(specs) == pytest.approx(0.5, abs=0.05)
    new = {s.key for s in specs if s.key == s.index}
    assert all(s.key in new and s.key < s.index for s in repeats)
    # Zipf(1) over order of first appearance: the first query is repeated
    # most, and about twice as often as the second.
    first, second = sorted(new)[:2]
    picks = collections.Counter(s.key for s in repeats)
    assert picks.most_common(1)[0][0] == first
    assert picks[first] / picks[second] == pytest.approx(2.0, rel=0.3)


@pytest.mark.parametrize("raw", [
    {"arrivals": {"kind": "closed"}},
    {"driver": "d", "arrivals": {"kind": "open"}},
    {"driver": "d", "arrivals": {"kind": "closed", "rate_per_s": 1}},
    {"driver": "d", "arrivals": {"kind": "poisson", "rate_per_s": 0, "burst": 1}},
    {"driver": "d", "arrivals": {"kind": "closed"}, "repeat": {"share": 2,
                                                             "zipf_s": 1}},
], ids=["no-driver", "unknown-kind", "stray-key", "zero-rate", "bad-share"])
def test_malformed_traffic_is_refused(tmp_path, raw):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps(raw))
    with pytest.raises(ValueError):
        generator.load_traffic(str(p))


def test_malformed_repro_run_call_is_refused():
    from bench.drivers import repro_run

    with pytest.raises(ValueError):
        repro_run.load(ROOT, "lu470k.query", {}, {
            "calls": [{"batch": False, "configs": [{}, {}]}],
            "value_scale": [0.9, 1.1]})
