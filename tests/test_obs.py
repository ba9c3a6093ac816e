"""Telemetry layer suite: meter thread-safety, disabled-mode cost
discipline (shared null span, no allocation), the span's two sinks (the
Chrome buffer and the profiler's trace), Chrome-trace export round-trip
with monotonic nesting, the plan-cache counters that ``cache_stats()``
now reads, the service's per-batch phase spans on the profiler's clock,
and service stats-snapshot consistency under a concurrent soak.

Everything here runs against *private* :class:`repro.obs.Registry`
instances wherever possible so the suite neither depends on nor
pollutes the process-global registry other tests' compiles write to.
"""
import contextlib
import gc
import glob
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from repro import graph, obs
from repro.core.registry import PIPELINES, pipelines
from repro.graph import plan as plan_lib
from repro.graph.service import PipelineService

pipelines()
RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# meters: correctness + thread safety
# ---------------------------------------------------------------------------
def test_counter_concurrent_adds_exact():
    reg = obs.Registry(enabled=False)
    c = reg.counter("t.hits")
    n_threads, per = 8, 5000

    def bump():
        for _ in range(per):
            c.add()

    threads = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per          # no lost updates
    assert reg.counter("t.hits") is c          # get-or-create: same object
    c.reset()
    assert c.value == 0


def test_histogram_summary_and_concurrent_records():
    reg = obs.Registry(enabled=False)
    h = reg.histogram("t.lat", unit="ms", sample_size=256)
    assert h.summary()["p50"] is None          # empty: no fake numbers
    vals = list(range(100))

    def rec(chunk):
        for v in chunk:
            h.record(v)

    threads = [threading.Thread(target=rec, args=(vals[k::4],))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = h.summary()
    assert s["count"] == 100 and s["min"] == 0 and s["max"] == 99
    assert s["unit"] == "ms"
    assert abs(s["mean"] - np.mean(vals)) < 1e-9   # exact, not sampled
    assert abs(s["p50"] - 50) <= 2                 # sample-based quantile
    assert s["p50"] <= s["p95"] <= s["p99"]


def test_histogram_ring_buffer_slides():
    h = obs.Histogram("t.window", sample_size=8)
    for v in range(1000):
        h.record(v)
    s = h.summary()
    assert s["count"] == 1000 and s["max"] == 999   # exact stats keep all
    assert s["p50"] >= 992                  # quantiles see the last window


def test_gauge_last_write_wins():
    g = obs.Gauge("t.depth")
    g.set(3)
    g.set(7)
    assert g.value == 7.0


# ---------------------------------------------------------------------------
# spans: disabled-mode discipline, enabled-mode recording
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_singleton():
    reg = obs.Registry(enabled=False)
    s = reg.span("a", cat="x", k=1)
    assert s is reg.span("b") is obs.NULL_SPAN    # no per-call allocation
    with s as inner:
        inner.set(extra=2)                        # swallowed, no error
    reg.instant("marker")                         # gated too
    assert reg.events() == []


def test_enabled_spans_record_with_args_and_exceptions():
    reg = obs.Registry(enabled=True)
    with reg.span("outer", cat="test", graph="g"):
        with reg.span("inner", cat="test") as sp:
            sp.set(verdict="ok")
    with pytest.raises(RuntimeError, match="boom"):
        with reg.span("failing", cat="test"):
            raise RuntimeError("boom")            # still recorded
    reg.instant("mark", cat="test", note=object())
    ev = {e["name"]: e for e in reg.events()}
    assert set(ev) == {"outer", "inner", "failing", "mark"}
    assert ev["inner"]["args"]["verdict"] == "ok"
    assert ev["outer"]["ph"] == "X" and ev["mark"]["ph"] == "i"
    # non-JSON arg values are stringified, never poison the export
    assert isinstance(ev["mark"]["args"]["note"], str)
    # runtime toggle
    reg.disable()
    assert reg.span("gone") is obs.NULL_SPAN
    reg.enable()
    assert isinstance(reg.span("back"), obs.Span)


@contextlib.contextmanager
def profiling(trace_dir):
    """A jax profiler session without the Python tracer, as the
    benchmark's traced runs record."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_events(trace_dir) -> list[dict]:
    """Every host-plane event of the one ``.xplane.pb`` under
    ``trace_dir``: name, start and end (ns, the profiler's clock), stats
    as args, and the thread."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                out.append({"name": e.name, "ts": e.start_ns,
                            "end": e.start_ns + e.duration_ns,
                            "args": dict(e.stats), "thread": line.name})
    return out


@pytest.mark.parametrize("mode", ["off", "profiler", "on"])
def test_span_sinks_follow_telemetry_and_profiler(mode, tmp_path):
    reg = obs.Registry(enabled=(mode == "on"))
    ctx = profiling(tmp_path) if mode != "off" else contextlib.nullcontext()
    with ctx:
        outer = reg.span("t.outer", cat="test", batch=7)
        with outer as sp:
            with reg.span("t.inner", cat="test"):
                pass
            sp.set(bucket=8)
    if mode == "off":
        assert outer is obs.NULL_SPAN and reg.events() == []
        return
    # the session has stopped: without telemetry the gate closes again
    assert (reg.span("t.after") is obs.NULL_SPAN) == (mode == "profiler")
    chrome = {e["name"]: e for e in reg.events()}
    prof = {e["name"]: e for e in host_events(tmp_path)
            if e["name"].startswith("t.")}
    assert set(prof) == {"t.outer", "t.inner"}
    # args given at entry and by set() both reach the profiler's trace
    assert prof["t.outer"]["args"] == {"batch": 7, "bucket": 8}
    assert prof["t.outer"]["ts"] <= prof["t.inner"]["ts"] \
        <= prof["t.inner"]["end"] <= prof["t.outer"]["end"]
    if mode == "profiler":
        assert chrome == {}
    else:
        assert set(chrome) == {"t.outer", "t.inner"}
        assert chrome["t.outer"]["args"] == {"batch": 7, "bucket": 8}


def test_gc_span_in_the_profiler_trace(tmp_path):
    obs.trace_gc()
    obs.trace_gc()                                # idempotent
    hooks = [h for h in gc.callbacks if h is obs.REGISTRY._gc_hook]
    assert len(hooks) == 1
    with profiling(tmp_path):
        with jax.profiler.TraceAnnotation("t.window"):
            gc.collect()
    ev = host_events(tmp_path)
    (win,) = [e for e in ev if e["name"] == "t.window"]
    runs = [e for e in ev if e["name"] == "python.gc"
            and e["args"].get("generation") == 2]
    assert runs, "gc.collect() left no python.gc span"
    assert any(win["ts"] <= e["ts"] <= e["end"] <= win["end"]
               and "collected" in e["args"] for e in runs)


def test_event_buffer_bounded_counts_drops():
    reg = obs.Registry(enabled=True, max_events=4)
    for i in range(10):
        with reg.span(f"s{i}"):
            pass
    assert len(reg.events()) == 4
    assert reg.dropped_events == 6
    reg.reset()
    assert reg.events() == [] and reg.dropped_events == 0


def test_env_var_validated(monkeypatch):
    import repro.obs.telemetry as tel
    monkeypatch.setenv(tel.ENV_VAR, "yes")
    with pytest.raises(ValueError, match="TINA_TELEMETRY"):
        tel._env_enabled()
    monkeypatch.setenv(tel.ENV_VAR, "on")
    assert tel._env_enabled() is True


# ---------------------------------------------------------------------------
# trace export: JSON round-trip + monotonic nesting across threads
# ---------------------------------------------------------------------------
def test_trace_roundtrip_nested_multithread(tmp_path):
    reg = obs.Registry(enabled=True)
    # all four workers alive at once: thread idents are only unique
    # among live threads, and the test wants four distinct tracks
    gate = threading.Barrier(4)

    def worker(k):
        gate.wait()
        with reg.span("outer", cat="test", worker=k):
            for j in range(3):
                with reg.span("mid", cat="test", j=j):
                    with reg.span("leaf", cat="test"):
                        pass

    threads = [threading.Thread(target=worker, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    path = tmp_path / "trace.json"
    n = obs.export_chrome_trace(str(path), reg)
    assert n == 4 * (1 + 3 * 2)
    doc = json.loads(path.read_text())            # valid JSON, full stop
    events = doc["traceEvents"]
    assert obs.validate_nesting(events) == n      # every span nests
    # per-thread tracks: each worker's spans share one tid, 4 distinct
    assert len({e["tid"] for e in events}) == 4
    # the CLI the CI smoke step runs agrees
    from repro.obs import trace as trace_mod
    assert trace_mod.main([str(path), "--require", "outer", "leaf"]) == 0
    with pytest.raises(SystemExit, match="missing required"):
        trace_mod.main([str(path), "--require", "nope"])


def test_validate_nesting_rejects_overlap():
    tid = {"pid": 1, "tid": 1, "ph": "X", "cat": "t", "args": {}}
    ok = [dict(tid, name="a", ts=0.0, dur=10.0),
          dict(tid, name="b", ts=2.0, dur=3.0),
          dict(tid, name="c", ts=6.0, dur=4.0)]   # sibling after b: fine
    assert obs.validate_nesting(ok) == 3
    bad = [dict(tid, name="a", ts=0.0, dur=10.0),
           dict(tid, name="b", ts=5.0, dur=10.0)]  # straddles a's end
    with pytest.raises(ValueError, match="does not nest"):
        obs.validate_nesting(bad)


# ---------------------------------------------------------------------------
# plan-cache counters: cache_stats() reads the same books compile bumps
# ---------------------------------------------------------------------------
def test_plan_cache_stats_hits_misses_evictions():
    plan_lib.clear_cache()
    g = PIPELINES["spectrogram"].build()
    shapes = {g.inputs[0]: (256,)}
    s0 = plan_lib.cache_stats()
    assert s0["hits"] == 0 and s0["misses"] == 0 and s0["size"] == 0
    p = graph.compile(g, shapes, options=graph.CompileOptions(dtype="float32"))
    assert graph.compile(g, shapes,
                         options=graph.CompileOptions(dtype="float32")) is p
    s1 = plan_lib.cache_stats()
    assert s1["misses"] == 1 and s1["hits"] == 1 and s1["size"] == 1
    evicted_before = s1["evictions"]
    plan_lib.clear_cache()
    s2 = plan_lib.cache_stats()
    assert s2["size"] == 0 and s2["hits"] == 0 and s2["misses"] == 0
    assert s2["evictions"] == evicted_before + 1   # eviction total persists


def test_plan_quantize_span_once_per_plan_build_that_packs_weights():
    plan_lib.clear_cache()
    g = graph.build_pfb_power(16, 8)
    was_on = obs.enabled()
    obs.enable()
    try:
        ev0 = len(obs.events())
        for shape, prec in (((2, 256), "int8"), ((2, 256), "int8"),
                            ((2, 256), "f32"), ((4, 256), "int8")):
            graph.compile(g, {"x": shape},
                          options=graph.CompileOptions(precision=prec))
        spans = [e for e in obs.events()[ev0:]
                 if e["name"] == "plan.quantize"]
    finally:
        if not was_on:
            obs.disable()
    # two int8 builds (the repeat is a cache hit); the f32 build packs
    # nothing; each packs the one pfb node's taps
    assert len(spans) == 2
    for e in spans:
        assert e["cat"] == "compile"
        assert e["args"] == {"graph": g.name, "nodes": 1}


# ---------------------------------------------------------------------------
# the service's batch phases, on the profiler's clock
# ---------------------------------------------------------------------------
DISPATCH = ("service.pack", "service.stage", "service.enqueue")
COMPLETE = ("service.wait", "service.fetch", "service.deliver")


def test_service_batch_phases_in_the_profiler_trace(tmp_path):
    svc = PipelineService(PIPELINES["spectrogram"].build(), signal_len=256,
                          batch_size=4)
    xs = [RNG.standard_normal(256).astype(np.float32) for _ in range(17)]
    with profiling(tmp_path):
        with jax.profiler.TraceAnnotation("t.window"):
            futs = [svc.submit(x) for x in xs]
            with svc:
                for f in futs:
                    f.result(timeout=60)
    batches = svc.stats()["batches"]
    ev = host_events(tmp_path)
    (win,) = [e for e in ev if e["name"] == "t.window"]
    by_batch: dict = {}
    for e in ev:
        if e["name"].startswith("service.") and "batch" in e["args"]:
            assert e["name"] not in by_batch.setdefault(
                e["args"]["batch"], {}), e
            by_batch[e["args"]["batch"]][e["name"]] = e
    assert len(by_batch) == batches >= 5
    for seq, ph in by_batch.items():
        assert set(ph) == {"service.dispatch", "service.complete",
                           *DISPATCH, *COMPLETE}, (seq, sorted(ph))
        for parent, kids in (("service.dispatch", DISPATCH),
                             ("service.complete", COMPLETE)):
            p = ph[parent]
            assert win["ts"] <= p["ts"] and p["end"] <= win["end"]
            for k in kids:
                assert p["ts"] <= ph[k]["ts"] <= ph[k]["end"] <= p["end"]
                assert ph[k]["thread"] == p["thread"]
        assert ph["service.dispatch"]["end"] <= ph["service.complete"]["ts"]
    # queued before start, so the overlapped loop launches batch N+1
    # before it completes batch N
    seqs = sorted(by_batch)
    assert any(by_batch[b]["service.dispatch"]["ts"]
               < by_batch[a]["service.complete"]["ts"]
               for a, b in zip(seqs, seqs[1:]))


@pytest.mark.parametrize("drive", ["thread", "flush"])
def test_service_batch_phase_histograms(drive):
    svc = PipelineService(PIPELINES["spectrogram"].build(), signal_len=256,
                          batch_size=4)
    xs = [RNG.standard_normal(256).astype(np.float32) for _ in range(10)]
    if drive == "thread":
        with svc:
            for f in [svc.submit(x) for x in xs]:
                f.result(timeout=60)
    else:
        futs = [svc.submit(x) for x in xs]
        assert svc.flush() == 3                  # 4, 4, then 2 in bucket 2
        for f in futs:
            f.result(timeout=0)
        svc.close()
    s = svc.stats()
    lat = s["latency_ms"]
    assert set(lat) == {"total", "queued", "pad", "stage", "wait", "fetch",
                        "fetch_shard"}
    for k in ("pad", "stage", "wait", "fetch"):
        assert lat[k]["count"] == s["batches"] > 0, k
        assert lat[k]["min"] >= 0
    assert lat["fetch_shard"]["count"] == 0     # one device: no shards
    assert lat["total"]["count"] == lat["queued"]["count"] == 10


# ---------------------------------------------------------------------------
# service stats: locked snapshots stay consistent mid-soak
# ---------------------------------------------------------------------------
def test_service_stats_snapshot_consistent_under_soak():
    spec = PIPELINES["spectrogram"]
    svc = PipelineService(spec.build(), signal_len=256, batch_size=8,
                          record_batches=True)
    xs = [RNG.standard_normal(256).astype(np.float32) for _ in range(48)]
    snaps, errs = [], []
    stop = threading.Event()

    def submitter(lo, hi):
        try:
            for i in range(lo, hi):
                svc.submit(xs[i]).result(timeout=60)
        except Exception as e:                    # noqa: BLE001
            errs.append(e)

    def watcher():
        while not stop.is_set():
            snaps.append(svc.stats())             # racing the batcher
            time.sleep(0.001)

    with svc:
        threads = [threading.Thread(target=submitter, args=(k, k + 12))
                   for k in range(0, 48, 12)]
        w = threading.Thread(target=watcher)
        w.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
        stop.set()
        w.join(timeout=30)
    assert not errs
    final = svc.stats()
    svc.close()
    assert isinstance(final, dict)
    assert final["requests"] == 48
    assert final["latency_ms"]["total"]["count"] == 48
    # per-request phases are sub-spans of the total
    assert final["latency_ms"]["queued"]["p50"] <= \
        final["latency_ms"]["total"]["p50"]
    # slot accounting closes exactly against the recorded packings
    assert final["requests"] + final["padded_slots"] == \
        sum(b for b, _ in svc.batch_log)
    assert final["fill_ratio"] == pytest.approx(
        final["requests"] / (final["requests"] + final["padded_slots"]))
    assert sum(final["bucket_batches"].values()) == final["batches"]
    # every mid-soak snapshot was internally consistent and monotone
    prev = None
    for s in snaps + [final]:
        assert 0 <= s["requests"] <= 48
        assert s["padded_slots"] >= 0 and s["batches"] >= 0
        assert 0 <= s["fill_ratio"] <= 1
        assert sum(s["bucket_batches"].values()) == s["batches"]
        if prev is not None:
            assert s["requests"] >= prev["requests"]
            assert s["batches"] >= prev["batches"]
        prev = s
    # a fresh snapshot after close still reads the same books
    assert svc.stats()["requests"] == 48
