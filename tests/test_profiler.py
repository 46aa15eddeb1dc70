"""observability/profiler.py and the tracer's profiler mirror: the
reconcile tracer's spans entering a `jax.profiler.TraceAnnotation` while
a capture records, and the on-demand /debug/profile capture.

Pins:

  * the profiler probe runs ONCE per process and is cached, available
    or not;
  * with no capture recording, spans enter no annotation, and a
    disabled tracer hands out the SHARED no-op;
  * while a capture records, context-managed spans (enabled tracer or
    not) enter an annotation of their name; `begin()` spans do not;
    annotation setup/teardown failures are swallowed while exceptions
    from the traced block propagate;
  * on a real capture the spans land on the trace's host plane;
  * `start_profiler_server` logs its failure reason instead of
    returning False silently;
  * `/debug/profile?ms=N` captures bounded + single-flight into the
    journal dir (atomic rename, manifest stamped with the active trace
    id) and answers 503 when the probe failed or nothing is wired.
"""

import glob
import json
import os
import sys
import urllib.error
import urllib.request

import pytest

from karpenter_tpu.metrics.registry import GaugeRegistry
from karpenter_tpu.observability import MetricsServer, Tracer
from karpenter_tpu.observability import profiler as P
from karpenter_tpu.observability import tracing as T


@pytest.fixture
def fresh_probe():
    """Reset the probe cache around each test (module-global state)."""
    T.reset_profiler_probe()
    yield
    T.reset_profiler_probe()


class FakeProfiler:
    """A stand-in probe result: a switchable recording flag and an
    annotation class that logs what it enters and exits."""

    def __init__(self, recording=True, broken_setup=False,
                 broken_teardown=False):
        self.recording = recording
        self.entered, self.exited = [], []
        outer = self

        class Annotation:
            def __init__(self, name):
                if broken_setup:
                    raise RuntimeError("profiler backend fell over")
                self.name = name

            def __enter__(self):
                outer.entered.append(self.name)
                return self

            def __exit__(self, *exc):
                if broken_teardown:
                    raise RuntimeError("profiler backend fell over")
                outer.exited.append(self.name)
                return False

        self.probe = (lambda: self.recording, Annotation)


@pytest.fixture
def fake_profiler(fresh_probe, monkeypatch):
    def install(**kwargs):
        fake = FakeProfiler(**kwargs)
        monkeypatch.setattr(T, "_PROFILER", fake.probe)
        return fake

    return install


class TestProfilerMirror:
    def test_broken_jax_profiler_probe_is_cached(
        self, fresh_probe, monkeypatch
    ):
        # sys.modules[name] = None makes `import jax.profiler` raise
        # ImportError — the "jax absent/broken" environment
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        tracer = Tracer()
        tracer.enabled = False
        assert tracer.span("solver.dispatch") is T._NOOP_SPAN
        # cached as unavailable: restoring the module does not
        # resurrect the mirror until the probe is reset
        monkeypatch.undo()
        assert T._PROFILER is False
        assert not T.profiler_available()
        assert tracer.trace("again") is T._NOOP_SPAN

    def test_probe_runs_once(self, fresh_probe, monkeypatch):
        calls = []
        real = T._probe_profiler

        def counting():
            calls.append(1)
            return real()

        monkeypatch.setattr(T, "_probe_profiler", counting)
        tracer = Tracer()
        for name in ("a", "b", "c"):
            with tracer.span(name):
                pass
        assert len(calls) == 1
        probed = T._PROFILER
        assert probed is not None and probed is not False
        # the recording check is TraceMe.is_enabled itself
        from jax._src.lib import _profiler

        assert probed[0] is _profiler.TraceMe.is_enabled

    def test_no_annotation_while_not_recording(self, fake_profiler):
        fake = fake_profiler(recording=False)
        tracer = Tracer()
        with tracer.trace("reconcile.tick"):
            with tracer.span("solver.dispatch"):
                pass
        assert fake.entered == []
        assert len(tracer.snapshot()) == 2
        tracer.enabled = False
        assert tracer.span("x") is tracer.trace("y") is T._NOOP_SPAN

    def test_spans_enter_annotations_while_recording(self, fake_profiler):
        fake = fake_profiler()
        tracer = Tracer()
        with tracer.trace("reconcile.tick"):
            with tracer.span("solver.dispatch"):
                assert fake.entered == ["reconcile.tick", "solver.dispatch"]
            assert fake.exited == ["solver.dispatch"]
        assert fake.exited == ["solver.dispatch", "reconcile.tick"]
        assert [s["name"] for s in tracer.snapshot()] == [
            "solver.dispatch", "reconcile.tick",
        ]

    def test_disabled_tracer_still_mirrors(self, fake_profiler):
        fake = fake_profiler()
        tracer = Tracer()
        tracer.enabled = False
        with tracer.span("pendingcapacity.solve") as handle:
            assert handle is None  # as the disabled tracer's no-op
        assert fake.entered == fake.exited == ["pendingcapacity.solve"]
        assert tracer.snapshot() == []

    def test_unstacked_span_mirrors(self, fake_profiler):
        fake = fake_profiler()
        tracer = Tracer()
        with tracer.trace("reconcile.MetricsProducer") as root:
            with tracer.span("pendingcapacity.solve", nest=False):
                assert tracer.current() is root
                request = tracer.begin("solver.request")
            request.close()
        assert fake.entered == [
            "reconcile.MetricsProducer", "pendingcapacity.solve",
        ]
        assert request.parent_id == root.span_id

    def test_begin_spans_not_mirrored(self, fake_profiler):
        fake = fake_profiler()
        tracer = Tracer()
        handle = tracer.begin("solver.request")
        handle.close(ok=True)
        assert fake.entered == []
        assert tracer.snapshot()[0]["name"] == "solver.request"

    @pytest.mark.parametrize("broken", ["broken_setup", "broken_teardown"])
    def test_annotation_failures_swallowed(self, fake_profiler, broken):
        fake_profiler(**{broken: True})
        tracer = Tracer()
        ran = []
        with tracer.span("solver.cost"):
            ran.append(True)
        assert ran == [True]
        assert tracer.snapshot()[0]["args"] == {}

    @pytest.mark.parametrize("enabled", [True, False])
    def test_block_exceptions_propagate(self, fake_profiler, enabled):
        fake = fake_profiler()
        tracer = Tracer()
        tracer.enabled = enabled
        with pytest.raises(ValueError, match="the solve's own error"):
            with tracer.span("solver.dispatch"):
                raise ValueError("the solve's own error")
        assert fake.exited == ["solver.dispatch"]
        if enabled:
            assert tracer.snapshot()[0]["args"]["error"] == "ValueError"

    def test_spans_land_on_a_real_capture(self, fresh_probe, tmp_path):
        import jax.profiler
        from jax.profiler import ProfileData

        tracer = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tracer.trace("reconcile.tick"):
                with tracer.span("solver.publish_gauges"):
                    pass
        finally:
            jax.profiler.stop_trace()
        with tracer.span("after.capture"):
            pass
        path = glob.glob(
            str(tmp_path / "**" / "*.xplane.pb"), recursive=True
        )[-1]
        names = {
            ev.name
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for ev in line.events
        }
        assert {"reconcile.tick", "solver.publish_gauges"} <= names
        assert "after.capture" not in names


class TestProfilerServer:
    def test_failure_reason_is_logged(
        self, fresh_probe, monkeypatch, caplog
    ):
        import logging

        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        with caplog.at_level(logging.WARNING, logger="karpenter"):
            assert P.start_profiler_server(port=59999) is False
        assert "failed to start" in caplog.text


class TestCaptureProfile:
    def test_capture_writes_atomic_dir_with_manifest(
        self, fresh_probe, tmp_path
    ):
        report = P.capture_profile(
            ms=10, out_dir=str(tmp_path), trace_id="t00000a1"
        )
        assert os.path.isdir(report["path"])
        assert not report["path"].endswith(".tmp")
        assert os.path.basename(report["path"]).startswith(
            P.PROFILE_PREFIX
        )
        manifest = json.load(
            open(os.path.join(report["path"], "manifest.json"))
        )
        assert manifest["trace_id"] == "t00000a1"
        assert manifest["ms_requested"] == 10
        assert manifest["ms_captured"] >= 10
        # no orphan tmp dirs on the happy path
        assert not [
            name for name in os.listdir(tmp_path)
            if name.endswith(".tmp")
        ]

    def test_bounds_clamp(self, fresh_probe, tmp_path):
        report = P.capture_profile(ms=-50, out_dir=str(tmp_path))
        assert report["ms_requested"] == P.MIN_CAPTURE_MS

    def test_single_flight(self, fresh_probe, tmp_path):
        assert P._capture_lock.acquire(blocking=False)
        try:
            with pytest.raises(P.ProfileBusy):
                P.capture_profile(ms=10, out_dir=str(tmp_path))
        finally:
            P._capture_lock.release()

    def test_unavailable_probe_raises(
        self, fresh_probe, monkeypatch, tmp_path
    ):
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        with pytest.raises(P.ProfileUnavailable):
            P.capture_profile(ms=10, out_dir=str(tmp_path))


class TestDebugProfileEndpoint:
    def _get(self, url):
        # generous client timeout: stop_trace serializes the whole
        # process profile, which in a full-suite process that compiled
        # hundreds of XLA programs can take well over 10s
        try:
            with urllib.request.urlopen(url, timeout=120) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as error:
            return error.code, error.read()

    def test_capture_via_endpoint(self, fresh_probe, tmp_path):
        server = MetricsServer(
            GaugeRegistry(), port=0, host="127.0.0.1",
            profile_dir=str(tmp_path),
        )
        port = server.start()
        try:
            status, body = self._get(
                f"http://127.0.0.1:{port}/debug/profile?ms=10"
            )
            assert status == 200, body
            report = json.loads(body)
            assert os.path.isdir(report["path"])
            assert report["ms_requested"] == 10
        finally:
            server.stop()

    def test_no_journal_dir_is_503(self, fresh_probe):
        server = MetricsServer(GaugeRegistry(), port=0, host="127.0.0.1")
        port = server.start()
        try:
            status, body = self._get(
                f"http://127.0.0.1:{port}/debug/profile?ms=10"
            )
            assert status == 503
            assert b"journal-dir" in body
        finally:
            server.stop()

    def test_failed_probe_is_503(
        self, fresh_probe, monkeypatch, tmp_path
    ):
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        server = MetricsServer(
            GaugeRegistry(), port=0, host="127.0.0.1",
            profile_dir=str(tmp_path),
        )
        port = server.start()
        try:
            status, body = self._get(
                f"http://127.0.0.1:{port}/debug/profile?ms=10"
            )
            assert status == 503
            assert b"unavailable" in body
        finally:
            server.stop()

    def test_malformed_ms_is_400(self, fresh_probe, tmp_path):
        server = MetricsServer(
            GaugeRegistry(), port=0, host="127.0.0.1",
            profile_dir=str(tmp_path),
        )
        port = server.start()
        try:
            status, _body = self._get(
                f"http://127.0.0.1:{port}/debug/profile?ms=soon"
            )
            assert status == 400
        finally:
            server.stop()
