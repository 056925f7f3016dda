"""The reduction of the program's spans (``bench/program_spans.py``) on
events made by hand, its readers, and a traced run of a tiny cell on the CPU
with the program spans and row counters recorded."""
import time
import types

import pytest

from bench import harness, program_spans
from bench import trace as tracing
from bench.trace import Event
from benchtools import on_cpu, tiny_tree

MS = 1e6
DEV = "/device:TPU:0"
HOST = "/host:CPU"
SEED = 2**33 + 777
SECONDS = 1.5


def span(name, start_ms, end_ms):
    return Event(HOST, "python3", name, start_ms * MS, (end_ms - start_ms) * MS)


def op(start_ms, end_ms):
    return Event(DEV, "XLA Ops", "%fusion = bf16[] fusion()", start_ms * MS,
                 (end_ms - start_ms) * MS)


# Window 0-10 ms.  The chip runs 2-3 and 4.5-6.5 ms, so it idles 0-2, 3-4.5
# and 6.5-10 ms.  A pump holds a group, which holds a dispatch and a predict;
# a second pump runs alone at 7-8 ms; a resolve before the window is left out.
EVENTS = [
    span("bench.window", 0, 10),
    span("bench.step", 0, 6),
    span("repro.pump", 0, 6),
    span("repro.group", 1, 5),
    span("repro.dispatch", 1.5, 2.5),
    span("repro.predict", 3, 4),
    span("repro.pump", 7, 8),
    span("repro.resolve", -2, -1),
    op(2, 3), op(2.2, 2.4), op(4.5, 6.5),
]


@pytest.fixture(scope="module")
def made():
    return program_spans.reduce(EVENTS)


def test_self_time_leaves_out_the_child_spans(made):
    s = made.spans
    assert set(s) == {"pump", "group", "dispatch", "predict"}
    assert (s["pump"].count, s["group"].count) == (2, 1)
    assert s["pump"].total_s == pytest.approx(0.007)
    assert s["pump"].self_s == pytest.approx(0.003)    # 0-1, 5-6, 7-8
    assert s["group"].total_s == pytest.approx(0.004)
    assert s["group"].self_s == pytest.approx(0.002)   # 1-1.5, 2.5-3, 4-5
    assert s["dispatch"].self_s == pytest.approx(0.001)
    assert s["predict"].self_s == pytest.approx(0.001)


def test_idle_time_is_charged_to_the_innermost_open_span(made):
    s = made.spans
    assert made.chips == 1 and made.window_s == pytest.approx(0.010)
    assert made.idle_s == pytest.approx(0.007)
    assert s["pump"].idle_s == pytest.approx(0.002)      # 0-1, 7-8
    assert s["group"].idle_s == pytest.approx(0.001)     # 1-1.5, 4-4.5
    assert s["dispatch"].idle_s == pytest.approx(0.0005)  # 1.5-2
    assert s["predict"].idle_s == pytest.approx(0.001)   # 3-4
    # No program span open: 6.5-7 and 8-10.
    assert made.unspanned_idle_s == pytest.approx(0.0025)
    assert made.host_idle_s == pytest.approx(0.0045)
    assert "none" in program_spans.table(made)


def test_the_harness_reduction_is_unchanged_by_program_spans():
    with_program = tracing.reduce(EVENTS)
    without = tracing.reduce([e for e in EVENTS if not e.name.startswith("repro.")])
    assert with_program == without
    assert with_program.idle_gaps[0] == ("none", pytest.approx(0.0035))


def test_innermost_pieces_cover_each_instant_once():
    pieces = program_spans.innermost([e for e in EVENTS if e.name.startswith("repro.")])
    assert [(a / MS, b / MS, s.name) for a, b, s in pieces] == [
        (-2, -1, "repro.resolve"), (0, 1, "repro.pump"), (1, 1.5, "repro.group"),
        (1.5, 2.5, "repro.dispatch"), (2.5, 3, "repro.group"), (3, 4, "repro.predict"),
        (4, 5, "repro.group"), (5, 6, "repro.pump"), (7, 8, "repro.pump")]


def _reader(name):
    from bench.spec import Benchmark

    return Benchmark().reader(name)


def test_readers_of_made_spans_and_counters(made):
    window = types.SimpleNamespace(spans=made, counters={"rows_dispatched": 40,
                                                         "rows_padded": 6})
    assert _reader("host_idle_share")(window) == pytest.approx(45.0)
    assert _reader("dispatch_host_ms")(window) == pytest.approx(1.0)
    assert _reader("predict_ms_per_group")(window) == pytest.approx(1.0)
    assert _reader("padded_row_share")(window) == pytest.approx(15.0)


def test_readers_find_nothing_without_program_spans():
    # As the harness's Window has it without spans, and a program without
    # row counters.
    window = types.SimpleNamespace(counters={"dispatches": 3})
    for name in program_spans.METRICS:
        assert _reader(name)(window) is None


@pytest.fixture(scope="module")
def tiny_traced(tmp_path_factory):
    tiny = tiny_tree(tmp_path_factory.mktemp("checkout"))
    with on_cpu():
        return program_spans.run(tiny, "tiny-t4.open", SEED, SECONDS, time.perf_counter(),
                                 chip_check=False)


def test_a_traced_cell_reports_the_program_span_metrics(tiny_traced):
    result, summary = tiny_traced
    metrics = result["metrics"]
    assert result["correct"] is True
    assert metrics["dispatch_host_ms"]["value"] > 0
    assert metrics["predict_ms_per_group"]["value"] > 0
    assert 0 <= metrics["padded_row_share"]["value"] < 100
    # The CPU has no TPU plane: no idle time to charge.
    assert "host_idle_share" not in metrics and summary.chips == 0
    for name in ("pump", "admit", "plan", "order", "group", "dispatch", "predict",
                 "resolve"):
        assert result["program_spans"][name]["count"] > 0
    assert result["program_spans"]["dispatch"]["count"] >= result["program_spans"]["group"]["count"]
    assert result["rows"]["rows_dispatched"] >= result["program_spans"]["dispatch"]["count"]


def test_recording_leaves_the_harness_as_it_was(tiny_traced):
    assert harness.Window.__qualname__ == "Window"
    assert harness.counters.__qualname__ == "counters"
    assert tracing.find_xplane.__qualname__ == "find_xplane"
