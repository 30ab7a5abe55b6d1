"""benchmarks/hostspans.py without a chip: the arithmetic on made-up
planes, and a slice of a chip's trace (`data/trace_hostspans.json`, cut
from a traced run of `stream-512k.saturate` on a v5e, plain lists as
`hostspans.load` gives them) in which a program span lies over one idle
gap of the device and nothing over another."""

import json
import os

import pytest

from benchmarks import hostspans, xplane

MS = 1e6                                      # the planes count in ns


def planes_of(ops, threads):
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": ops}]},
            {"name": "/host:CPU",
             "lines": [{"name": name, "events": events}
                       for name, events in threads.items()]}]


def test_a_gap_takes_the_name_of_the_span_that_covers_most_of_it():
    # four operations, so three gaps: 30, 20 and 10 ms
    ops = [["fusion.1", 0.0, 5 * MS], ["fusion.2", 35 * MS, 5 * MS],
           ["fusion.3", 60 * MS, 5 * MS], ["fusion.4", 75 * MS, 5 * MS]]
    threads = {
        "python": [
            # over the first gap: the collector for 18 ms of it, in two
            # pieces, the persister for 6; both reach outside the gap
            ["gc.gen2", 2 * MS, 13 * MS], ["gc.gen2", 20 * MS, 10 * MS],
            ["event-management.persist", 30 * MS, 11 * MS],
            # over the second: the runtime's own event, no span of ours
            ["PjitFunction(step)", 41 * MS, 18 * MS],
            # over the third: a span for a twentieth of it
            ["event-sources.decode", 66 * MS, 0.5 * MS]],
        "swx-settle_0": [
            ["rule-processing.score.readback", 44 * MS, 7 * MS]]}
    out = hostspans.attribute(planes_of(ops, threads))
    assert [name for name, _ in out] == [
        "gc.gen2", "rule-processing.score.readback", "host_idle"]
    assert [s for _, s in out] == pytest.approx([0.030, 0.020, 0.010])
    # the shape of the reduction's `idle_gaps`, longest first, cut to `top`
    assert hostspans.attribute(planes_of(ops, threads), top=1) == [out[0]]
    # coverage is counted a thread at a time: two threads' 6% do not make 12%
    threads = {"a": [["egress.publish", 66 * MS, 0.6 * MS]],
               "b": [["egress.publish", 68 * MS, 0.6 * MS]]}
    assert hostspans.attribute(planes_of(ops[2:], threads)) \
        == [["host_idle", pytest.approx(0.010)]]
    # no device plane (a CPU trace), or a device that ran one operation
    assert hostspans.attribute(planes_of([], threads)[1:]) == []
    assert hostspans.attribute(planes_of(ops[:1], threads)) == []


def test_spans_are_known_by_the_programs_own_inventory():
    stages = hostspans.span_names()
    for name in ("event-sources.decode", "event-management.persist",
                 "rule-processing.score.enqueue",
                 "rule-processing.score.readback",
                 "rule-processing.assemble", "egress.publish", "gc.gen0",
                 "gc.gen2"):
        assert hostspans.is_span(name, stages), name
    for name in ("PjitFunction(step)", "gc.collect", "busy.gc", "fusion.6"):
        assert not hostspans.is_span(name, stages), name


def test_attribute_on_a_slice_of_a_chips_trace():
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_hostspans.json")) as fh:
        planes = json.load(fh)
    stages = hostspans.span_names()
    host = next(p for p in planes if p["name"] == hostspans.HOST_PLANE)
    # as `load` leaves them: program spans alone, a line a thread, the
    # loop's spans and the settle threads' read-backs on different lines
    assert all(hostspans.is_span(e[0], stages)
               for line in host["lines"] for e in line["events"])
    by_line = {line["name"]: {e[0] for e in line["events"]}
               for line in host["lines"]}
    settle = [names for line, names in by_line.items()
              if line.startswith("swx-settle")]
    assert settle and all(names == {"rule-processing.score.readback"}
                          for names in settle)
    assert any("rule-processing.score.enqueue" in names
               and "rule-processing.score.readback" not in names
               for names in by_line.values())
    out = hostspans.attribute(planes)
    device = next(p for p in planes if xplane.DEVICE_PLANE.match(p["name"]))
    busy = xplane.merge([(s, s + d) for _, s, d in device["lines"][0]["events"]])
    assert [s for _, s in out] == sorted((s for _, s in out), reverse=True)
    assert out[0][1] == pytest.approx(
        max(b[0] - a[1] for a, b in zip(busy, busy[1:])) * 1e-9)
    # the slice's longest gap is a stall of 129 ms that no span of the
    # program covers; over the next a settle thread waits on the device
    assert out[0] == ["host_idle", pytest.approx(0.129, abs=0.001)]
    assert out[1][0] == "rule-processing.score.readback"
    assert all(name == "host_idle" or hostspans.is_span(name, stages)
               for name, _ in out)
