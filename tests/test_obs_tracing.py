"""Tracer semantics + exporter structure (repro.obs.tracing / .export).

Pins: balanced async spans (double-begin / orphan-end raise instead of
silently corrupting the trace), injectable-clock determinism (the same
simulated schedule yields a bit-identical event list), the exact
structure the Chrome exporter emits (trace_event µs scaling), and that
every span, of a Tracer and of NULL_TRACER alike, lands on the JAX
profiler's host plane.
"""
import json

import pytest

from repro.obs import NULL_TRACER, Tracer, chrome_trace, write_chrome_trace


def test_async_span_lifecycle_balanced():
    tr = Tracer()
    tr.begin("request", 1, now=0.0, tid=3)
    tr.begin("request", 2, now=0.5, tid=4)
    assert sorted(tr.open_spans()) == [1, 2]
    tr.end(2, now=1.0)
    tr.end(1, now=2.0, launch=0)
    assert tr.open_spans() == []
    evs = tr.spans("request")
    assert [e.ph for e in evs] == ["B", "B", "E", "E"]
    assert evs[0].tid == 3 and evs[3].attrs == {"launch": 0}


def test_double_begin_and_orphan_end_raise():
    tr = Tracer()
    tr.begin("request", 7, now=0.0)
    with pytest.raises(ValueError):
        tr.begin("request", 7, now=1.0)
    with pytest.raises(KeyError):
        tr.end(8, now=1.0)
    tr.end(7, now=1.0)                     # still closable after the errors
    assert tr.open_spans() == []


def test_sync_span_and_instant():
    tr = Tracer()
    with tr.span("flush", now=2.0, batch=4):
        tr.instant("admit", now=2.0, tid=1, request=0)
    evs = tr.spans()
    # instant recorded inside, the X event appended on exit
    assert [e.ph for e in evs] == ["i", "X"]
    assert evs[1].ts == 2.0 and evs[1].dur == 0.0    # simulated => dur 0
    assert evs[1].attrs == {"batch": 4}


def test_wall_clock_span_measures_duration():
    tr = Tracer()
    with tr.span("work"):
        pass
    (ev,) = tr.spans("work")
    assert ev.ph == "X" and ev.dur >= 0.0


def test_simulated_clock_is_deterministic():
    def drive():
        tr = Tracer()
        t = 0.0
        for i in range(5):
            tr.begin("request", i, now=t, tid=i % 2, request=i)
            t += 0.25
        for i in range(5):
            tr.instant("admit", now=t, request=i)
            tr.end(i, now=t, launch=0)
        return [(e.name, e.ph, e.ts, e.tid, e.dur, tuple(sorted(e.attrs)))
                for e in tr.spans()]

    assert drive() == drive()


def test_chrome_trace_export_structure(tmp_path):
    tr = Tracer()
    tr.begin("request", 0, now=0.001, tid=5, request=0)
    tr.instant("admit", now=0.002, tid=5)
    tr.end(0, now=0.003)
    with tr.span("flush", now=0.003):
        pass
    doc = chrome_trace(tr, pid=2)
    evs = doc["traceEvents"]
    assert [e["ph"] for e in evs] == ["B", "i", "E", "X"]
    assert evs[0]["ts"] == pytest.approx(1000.0)     # seconds -> µs
    assert evs[0]["pid"] == 2 and evs[0]["tid"] == 5
    assert evs[1]["s"] == "t"                        # instant scope
    assert evs[3]["dur"] == 0.0
    path = tmp_path / "trace.json"
    assert write_chrome_trace(str(path), tr, pid=2) == 4
    assert json.loads(path.read_text())["traceEvents"] == doc["traceEvents"]


def test_simulated_clock_events_are_pinned():
    """The events of a simulated schedule, field by field: spans enter a
    profiler annotation and record exactly what they recorded without
    one."""
    tr = Tracer()
    tr.begin("request", 0, now=0.5, tid=2, request=0)
    with tr.span("runtime.launch", now=1.0, batch=1):
        tr.instant("admit", now=1.0, tid=2, request=0)
        with tr.span("runtime.preload", now=1.0):
            pass
    tr.end(0, now=1.0, launch=0)
    got = [(e.name, e.ph, e.ts, e.tid, e.dur, e.attrs) for e in tr.spans()]
    assert got == [("request", "B", 0.5, 2, None, {"request": 0}),
                   ("admit", "i", 1.0, 2, None, {"request": 0}),
                   ("runtime.preload", "X", 1.0, 0, 0.0, {}),
                   ("runtime.launch", "X", 1.0, 0, 0.0, {"batch": 1}),
                   ("request", "E", 1.0, 2, None, {"launch": 0})]


def test_spans_reach_the_profiler_host_plane(tmp_path):
    import glob
    import jax
    from jax.profiler import ProfileData

    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("rag.retrieve"):
            with NULL_TRACER.span("runtime.launch", now=3.0, batch=2):
                jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = {ev.name: (ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name in ("rag.retrieve", "runtime.launch")}
    assert set(spans) == {"rag.retrieve", "runtime.launch"}
    # the inner span lies inside the outer one, on one clock
    assert spans["rag.retrieve"][0] <= spans["runtime.launch"][0]
    assert spans["runtime.launch"][1] <= spans["rag.retrieve"][1]
    # only the in-memory tracer kept an event
    assert [e.name for e in tr.spans()] == ["rag.retrieve"]
    assert NULL_TRACER.spans() == []


def test_null_tracer_is_inert():
    NULL_TRACER.begin("request", 1, now=0.0)
    NULL_TRACER.end(2)                     # no KeyError: everything no-ops
    NULL_TRACER.instant("x")
    with NULL_TRACER.span("y"):
        pass
    assert NULL_TRACER.open_spans() == [] and NULL_TRACER.spans() == []
    assert len(NULL_TRACER) == 0
    assert chrome_trace(NULL_TRACER)["traceEvents"] == []
