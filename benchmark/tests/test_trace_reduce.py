"""`benchmark/trace_reduce.py` against a small trace recorded on the chip
(`record_trace.py`), run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_trace_reduce.py -q

The profiler wrote the same capture twice: as `.xplane.pb`, which
trace_reduce reads, and as Perfetto JSON, which this test reads with a
plain JSON parse. The two readings have to agree, and both have to match
the numbers recorded beside the fixture (`fixture.expected.json`).
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture")
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.trace_reduce import covered, load, union  # noqa: E402


@pytest.fixture(scope="module")
def reduced():
    return load(os.path.join(FIXTURE, "fixture.xplane.pb"))


@pytest.fixture(scope="module")
def perfetto():
    """(window, device program intervals, host annotations) in
    microseconds, from the Perfetto JSON alone."""
    with gzip.open(os.path.join(FIXTURE, "fixture.perfetto.json.gz")) as f:
        events = json.load(f)["traceEvents"]
    procs = {e["pid"]: e["args"]["name"] for e in events
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    threads = {(e["pid"], e["tid"]): e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    spans = [e for e in events if e.get("ph") == "X"]
    ops = [(e["ts"], e["ts"] + e["dur"]) for e in spans
           if procs.get(e["pid"], "").startswith("/device:TPU")
           and threads.get((e["pid"], e["tid"])) == "XLA Modules" and e["dur"] > 0]
    (win,) = [(e["ts"], e["ts"] + e["dur"]) for e in spans if e["name"] == "bench.window"]
    host = [(e["name"], e["ts"], e["ts"] + e["dur"]) for e in spans
            if procs.get(e["pid"], "").startswith("/host")]
    return win, ops, host


@pytest.fixture(scope="module")
def expected():
    with open(os.path.join(FIXTURE, "fixture.expected.json")) as f:
        return json.load(f)


def test_window_and_units(reduced, perfetto, expected):
    win, _ops, _host = perfetto
    assert reduced.window_s() == pytest.approx((win[1] - win[0]) / 1e6, rel=1e-6)
    assert reduced.window_s() == pytest.approx(expected["window_s"], rel=1e-9)
    assert len(reduced.units) == expected["units"] == 3


def test_busy_is_the_union_of_device_programs(reduced, perfetto, expected):
    win, ops, _host = perfetto
    busy_us = covered(union(ops), win[0], win[1])
    assert reduced.busy_s() == pytest.approx(busy_us / 1e6, rel=1e-4)
    assert reduced.busy_s() == pytest.approx(expected["busy_s"], rel=1e-9)
    assert 0 < reduced.busy_s() < reduced.window_s()


def test_top_programs_cover_busy(reduced, expected):
    ops = reduced.top_ops()
    assert [name for name, _s in ops] == [name for name, _s in expected["device_ops"]]
    assert sum(s for _n, s in ops) >= reduced.busy_s() * 0.99 or len(ops) == 10


def test_longest_gaps_are_the_host_sleeps(reduced, expected):
    gaps = reduced.idle_gaps({"host.prepare", "device.step"})
    assert [name for name, _s in gaps[:3]] == ["host.prepare"] * 3
    for _name, seconds in gaps[:3]:  # 50 ms sleeps, give or take the clocks
        assert 0.045 <= seconds < 0.2
    assert gaps == expected["idle_gaps"]
