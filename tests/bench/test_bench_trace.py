"""The reduction from a trace to busy time, idle share, per-program
device time, top ops and named idle gaps (`bench/trace.py`)."""
import gzip
import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE.parents[1]) not in sys.path:
    sys.path.append(str(HERE.parents[1]))  # the harness, package `bench`

from bench import trace  # noqa: E402

# times in ns; the host's "window" span marks 1000..11000
SMALL = {"planes": [
    {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["window", 1000, 10000], ["step", 1500, 3000], ["decode", 2000, 2000],
        ["submit", 6000, 500]]}]},
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__pdc(17)", 2100, 1800], ["jit__pf(3)", 5000, 1000],
            ["jit__pdc(17)", 7000, 2000], ["jit__pdc(17)", 10500, 1000]]},
        {"name": "XLA Ops", "events": [
            ["while", 2100, 1800], ["fusion.1", 2200, 500],
            ["fusion.2", 2800, 900], ["fusion.3", 5000, 1000],
            ["fusion.1", 7000, 2000], ["fusion.4", 10500, 1000]]}]},
]}


def test_busy_union_and_window():
    r = trace.reduce(SMALL)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(10000e-9)
    # [2100, 3900] (the loop covers its body) + [5000, 6000] + [7000, 9000]
    # + [10500, 11000] clipped at the window's end
    assert r["busy_s"] == pytest.approx(5300e-9)


def test_programs_inside_the_window():
    r = trace.reduce(SMALL)
    assert r["programs"]["jit__pdc"] == pytest.approx([1800e-9, 2000e-9])
    assert r["programs"]["jit__pf"] == pytest.approx([1000e-9])


def test_top_ops_by_self_time():
    ops = dict(trace.reduce(SMALL)["device_ops"])
    assert ops["fusion.1"] == pytest.approx(2500e-9)
    assert ops["while"] == pytest.approx(400e-9)  # 1800 less its body


def test_idle_gaps_named_by_host_span():
    gaps = trace.reduce(SMALL)["idle_gaps"]
    assert gaps[0] == ["host", pytest.approx(1500e-9)]
    assert sorted(g[0] for g in gaps) == ["host", "host", "step", "step"]
    assert sum(g[1] for g in gaps) == pytest.approx(10000e-9 - 5300e-9)


def test_idle_share_reader():
    from bench.common import load_module

    reader = load_module(HERE.parents[1] / "bench/metrics/idle_share.chat.py",
                         "idle_share_chat")
    assert reader.read({"trace": trace.reduce(SMALL)}) == pytest.approx(47.0)
    assert reader.read({"trace": {"devices": 0}}) is None


@pytest.mark.parametrize("began, ended, parts", [
    (None, None, [(10.0, 20.0)]),      # no stretch was taken
    (14.0, 16.5, [(10.0, 14.0), (16.5, 20.0)]),
    (14.0, None, [(10.0, 14.0)]),      # still tracing at the window's end
    (8.0, 12.0, [(12.0, 20.0)]),
])
def test_stretch_outside(began, ended, parts):
    s = trace.Stretch("unused", 0.0, 1.0)
    s.began, s.ended = began, ended
    assert s.outside(10.0, 20.0) == parts


def test_train_mfu_reads_the_rate_outside_the_stretch():
    from bench.common import load_module

    reader = load_module(HERE.parents[1] / "bench/metrics/mfu.train.py",
                         "mfu_train")
    peak = {"bf16_flops": 1e12}
    record = {"peak": peak, "outside_steps": 10, "outside_s": 2.0,
              "tokens_per_step": 100, "flops_per_token": 1e8}
    assert reader.read(record) == pytest.approx(5.0)  # 5e10 of 1e12
    assert reader.read(dict(record, outside_steps=0)) is None


def test_recorded_chip_trace():
    """An excerpt of a trace recorded on one TPU v5 lite chip while the
    chat cell served: the decode program is found, and busy time lies
    inside the stretch."""
    with gzip.open(HERE / "data" / "chat_trace_excerpt.json.gz", "rt") as f:
        recorded = json.load(f)
    r = trace.reduce(recorded)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    assert len(r["programs"]["jit__pdc"]) >= 1
    assert all(t > 0 for t in r["programs"]["jit__pdc"])
