"""Device idle time put down to program spans (``bench/span_idle.py``) on
hand-built and recorded traces, and the readers of the service's
per-batch staging and pull-back histograms."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, span_idle, trace_reduce  # noqa: E402

MS = 1_000_000  # ns


def nested_trace(window=True):
    """Window 0-100 ms.  Chip 0 runs ops at 10-30 and 60-65 ms, chip 1
    is busy throughout.  The batcher thread dispatches from 0 to 50 ms
    and packs from 35 to 50 inside it; another thread collects garbage
    from 40 to 45; a host event that is no program span covers it all."""
    host = {
        "batcher": [["service.dispatch", 0, 50 * MS],
                    ["service.pack", 35 * MS, 15 * MS],
                    ["np.asarray(jax.Array)", 0, 100 * MS]],
        "loadgen": [["python.gc", 40 * MS, 5 * MS]]}
    if window:
        host["main"] = [[trace_reduce.WINDOW, 0, 100 * MS]]
    return [
        {"name": "/device:TPU:0", "lines": {
            "XLA Ops": [["pfb", 10 * MS, 20 * MS], ["pfb", 60 * MS, 5 * MS]]}},
        {"name": "/device:TPU:1", "lines": {
            "XLA Ops": [["pfb", 0, 100 * MS]]}},
        {"name": "/host:CPU", "lines": host},
    ]


def test_bench_span_idle_shortest_open_span_else_none():
    r = span_idle.summarize(nested_trace())
    # chip 0 idles 0-10 and 30-50 under the dispatch, of which 35-50
    # is packing and 40-45 garbage collection; 50-60 and 65-100 under
    # no program span.  Chip 1 never idles: halve for the mean.
    assert dict(r["idle_by_span"]) == pytest.approx({
        "service.dispatch": 0.0075, "service.pack": 0.005,
        "python.gc": 0.0025, "none": 0.0225})
    assert [k for k, _ in r["idle_by_span"]][0] == "none"
    assert r["idle_s"] == pytest.approx(0.0375)
    assert r["idle_unattributed_pct"] == pytest.approx(100 * 45 / 75)
    assert r["spans"] == {
        "python.gc": [1, pytest.approx(0.005), pytest.approx(0.005)],
        "service.dispatch": [1, pytest.approx(0.05), pytest.approx(0.05)],
        "service.pack": [1, pytest.approx(0.015), pytest.approx(0.015)]}
    assert r["long_spans"] == []
    longest = r["longest_idle"][0]
    assert longest["idle_s"] == pytest.approx(0.035)
    assert longest["idle_by_span"] == [["none", pytest.approx(0.035)]]
    assert dict(r["longest_idle"][1]["idle_by_span"]) == pytest.approx({
        "service.dispatch": 0.005, "service.pack": 0.010,
        "python.gc": 0.005, "none": 0.010})


def test_bench_span_idle_agrees_with_the_busy_time():
    planes = nested_trace()
    r = span_idle.summarize(planes)
    busy = trace_reduce.reduce(planes)["busy_s"]
    assert r["idle_s"] == pytest.approx(
        r["window_s"] - sum(busy) / len(busy))


def test_bench_span_idle_window_defaults_to_the_device_ops():
    r = span_idle.summarize(nested_trace(window=False))
    # chip 1's op spans the whole 100 ms: the same window
    assert r["window_s"] == pytest.approx(0.1)
    assert dict(r["idle_by_span"])["service.pack"] == pytest.approx(0.005)


def test_bench_span_idle_recorded_v5e_excerpt_has_no_program_span():
    """The recorded excerpt predates the program spans: every idle
    instant is unattributed."""
    planes = json.loads((Path(__file__).parent / "data"
                         / "trace_v5e_saturate_30ms.json").read_text())
    r = span_idle.summarize(planes)
    assert [k for k, _ in r["idle_by_span"]] == ["none"]
    assert r["idle_unattributed_pct"] == pytest.approx(100.0)
    assert r["spans"] == {}
    busy = trace_reduce.reduce(planes)["busy_s"][0]
    assert r["idle_s"] == pytest.approx(r["window_s"] - busy)


def test_bench_span_idle_lists_long_spans_but_not_waiting():
    """A 150 ms pack and a 200 ms wait for traffic in a 400 ms window:
    the pack is listed with where it starts, the wait for traffic is
    not, and each span's longest instance is kept."""
    planes = [
        {"name": "/device:TPU:0", "lines": {
            "XLA Ops": [["pfb", 380 * MS, 10 * MS]]}},
        {"name": "/host:CPU", "lines": {
            "main": [[trace_reduce.WINDOW, 0, 400 * MS]],
            "batcher": [["service.idle", 0, 200 * MS],
                        ["service.pack", 210 * MS, 150 * MS],
                        ["service.pack", 370 * MS, 5 * MS]]}}]
    r = span_idle.summarize(planes)
    assert r["long_spans"] == [["service.pack", pytest.approx(0.21),
                                pytest.approx(0.15)]]
    assert r["spans"]["service.pack"] == [2, pytest.approx(0.155),
                                          pytest.approx(0.15)]
    assert r["spans"]["service.idle"][2] == pytest.approx(0.2)


def test_bench_span_idle_summarises_what_the_harness_reduces():
    """The traced-cell form: every trace that ``trace_reduce.reduce``
    reduces is summarised too, and the reduction itself is unchanged;
    on leaving, ``reduce`` is the module's own again."""
    planes = nested_trace()
    own = trace_reduce.reduce
    with span_idle.summaries() as out:
        got = trace_reduce.reduce(planes)
    assert trace_reduce.reduce is own
    assert got == own(planes)
    assert out == [span_idle.summarize(planes)]


def test_bench_span_idle_command_needs_one_directory(capsys):
    assert span_idle.main([]) == 2
    assert "span_idle.py <trace_dir>" in capsys.readouterr().err


@pytest.mark.parametrize("argv", [
    ["a_dir", "--workload", "x.saturate"],
    ["--workload", "x.saturate", "--seed", "1"]])
def test_bench_span_idle_command_takes_a_directory_or_a_cell(argv, capsys):
    assert span_idle.main(argv) == 2
    assert "span_idle.py <trace_dir>" in capsys.readouterr().err


def _hist(count, mean):
    return {"count": count, "mean": mean if count else None}


@pytest.mark.parametrize("name,key", [("stage_ms", "stage"),
                                      ("fetch_ms", "fetch")])
def test_bench_span_readers_difference_the_window(name, key):
    read = harness.module("metrics", name).read
    run = SimpleNamespace(
        stats_open={"latency_ms": {key: _hist(4, 2.0)}},
        stats_close={"latency_ms": {key: _hist(10, 3.0)}})
    # 10 batches at 3 ms mean, of which 4 before the window at 2 ms
    assert read(run) == pytest.approx((30.0 - 8.0) / 6)
    run.stats_open = {"latency_ms": {key: _hist(0, None)}}
    assert read(run) == pytest.approx(3.0)
    # no batch in the window
    run.stats_open = run.stats_close
    assert read(run) is None
    # a service without the histogram (before the phase was timed)
    run.stats_open = run.stats_close = {"latency_ms": {"pad": _hist(3, 1)}}
    assert read(run) is None
