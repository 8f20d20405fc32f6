"""The trace reduction and the roofline counts, on a profiler trace recorded
on the CPU (``data/cpu_trace.*``) and on hand-computed shapes."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import roofline, tracereduce   # noqa: E402

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    marks = json.loads((DATA / "cpu_trace.json").read_text())["marks_ns"]
    pd = tracereduce.load(str(DATA / "cpu_trace.xplane.pb"))
    red = tracereduce.reduce_trace(pd, marks, *tracereduce.LAYOUT["cpu"],
                                   kernel="sort")
    return marks, red


def test_window_is_busy_plus_gaps(recorded):
    _, red = recorded
    # 4 batches of two ~0.6 ms sorts around a 20 ms sleep, 5 ms apart
    assert 0.095 < red["window_s"] < 0.125
    gaps = sum(d for _, d in red["gaps"])
    assert red["busy_s"] + gaps == pytest.approx(red["window_s"], rel=1e-9)
    assert 0 < red["busy_s"] < 0.02
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0.8 < idle < 1.0


def test_kernel_time_and_ops(recorded):
    _, red = recorded
    ktime = sum(d for _, d in red["kernel_events"])
    ops = dict(red["ops"])
    assert ops["sort"] == pytest.approx(ktime)
    assert red["ops"][0][0] == "sort"           # the heaviest op first
    assert len(red["kernel_events"]) % 8 == 0   # 8 calls of the program
    assert ktime <= red["busy_s"] + 1e-12


def test_gaps_are_on_the_host_clock_and_labelled(recorded):
    marks, red = recorded
    ms = 1e-3
    spans = []
    for m in marks:
        t = m * 1e-9
        spans.append(("batch", t, 30 * ms))
        spans.append(("sleep", t + 0.3 * ms, 21.5 * ms))
    longest = red["gaps"][:4]
    # the four 20 ms sleeps are the longest gaps, each inside its batch
    for start, dur in longest:
        assert 0.018 < dur < 0.025
        assert any(m * 1e-9 < start < m * 1e-9 + 5 * ms for m in marks)
    labels = tracereduce.label_gaps(red["gaps"], spans, top=7)
    assert [name for name, _ in labels[:4]] == ["sleep"] * 4
    assert {name for name, _ in labels[4:]} == {"batch"}


def test_annotation_count_must_match():
    pd = tracereduce.load(str(DATA / "cpu_trace.xplane.pb"))
    with pytest.raises(ValueError):
        tracereduce.reduce_trace(pd, [0, 1, 2], *tracereduce.LAYOUT["cpu"])


def test_merge_clip_family():
    assert tracereduce.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                                   [5, 8]]
    assert tracereduce.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert tracereduce.op_family("masked_first_fit.12") == "masked_first_fit"
    assert tracereduce.op_family("end: sort.0") == "end: sort"
    # a TPU op event carries its HLO text
    assert tracereduce.op_family(
        "%masked_first_fit.9 = s32[8,1]{1,0:T(8,128)S(1)} custom-call(%pad.64,"
        " %pad.65), custom_call_target=\"tpu_custom_call\"") == \
        "masked_first_fit"


def test_masked_first_fit_work_by_hand():
    # 3 live rows x 32 slots: 2 int32 reads per slot, one int32 position
    # read and one index written per row
    assert roofline.masked_first_fit_bytes(3, 32) == 792
    assert roofline.masked_first_fit_bytes(1, 4) == 40
    v5e = roofline.peaks("TPU v5 lite")
    assert roofline.bound_s(792, v5e) == pytest.approx(792 / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v99")


def test_roofline_reader_by_hand():
    from bench.run import load_reader
    red = {"modules": [(0.0, 1e-3), (2e-3, 1e-3)],
           "kernel_events": [(1e-4, 1e-5), (2e-4, 1e-5), (2.1e-3, 2e-5)]}
    assert tracereduce.kernels_per_module(red) == [2, 1]
    calls = [(-1e-4, 4, 32), (1.9e-3, 16, 32)]
    ctx = {"trace": red, "calls": calls, "device_kind": "TPU v5 lite"}
    v5e = roofline.peaks("TPU v5 lite")
    want = (2 * roofline.bound_s(roofline.masked_first_fit_bytes(4, 32), v5e)
            + roofline.bound_s(roofline.masked_first_fit_bytes(16, 32), v5e))
    got = load_reader("masked_first_fit_roofline").read(ctx)
    assert got == pytest.approx(100 * want / 4e-5)
    # no kernel ran: the reader gives nothing, never 0
    assert load_reader("masked_first_fit_roofline").read(
        {"trace": {"modules": [], "kernel_events": []}, "calls": calls,
         "device_kind": "TPU v5 lite"}) is None
