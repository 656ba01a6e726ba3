"""Device-level observability suite: the XLA compile sentry (hot-path
recompile detection with shape attribution), HBM/live-buffer memory
gauges, Chrome/Perfetto trace export (unit + live serving round-trip),
and the serving debug endpoints.  See
docs/observability.md "Device-level signals".
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.core.telemetry import device as device_obs



@pytest.fixture
def sentry():
    """The armed process-wide sentry, returned in warmup mode and left
    in warmup mode (other tests compile freely)."""
    s = telemetry.track_compiles()
    s.reset()
    telemetry.reset_counters("xla.")
    yield s
    s.reset()
    telemetry.reset_counters("xla.")


# ------------------------------------------------------------ compile sentry
def test_hot_path_recompile_flagged_and_shape_named(sentry):
    """The acceptance scenario: warm one shape, declare warmup over,
    then force a second-shape recompile — the hot_path counter moves and
    the log_verb record names the triggering shape."""
    import jax
    import jax.numpy as jnp

    telemetry.clear_records()
    f = telemetry.watch_compiles(jax.jit(lambda x: x * 2.0),
                                 name="test.fn")
    f(jnp.ones((4,), jnp.float32))  # warmup compile
    assert telemetry.counters("xla.compile.hot_path") == {}

    sentry.end_warmup()
    assert not sentry.in_warmup
    f(jnp.ones((4,), jnp.float32))  # cached executable: no compile
    assert telemetry.counters("xla.compile.hot_path") == {}

    f(jnp.ones((8,), jnp.float32))  # NEW shape: steady-state recompile
    hot = telemetry.counters("xla.compile.hot_path")
    assert sum(hot.values()) >= 1
    assert hot.get("xla.compile.hot_path.test.fn") == 1

    recs = [r for r in telemetry.recent_records()
            if r.get("method") == "hot_path_recompile"]
    assert recs, "steady-state recompile must emit a loud record"
    assert recs[-1]["fn"] == "test.fn"
    assert recs[-1]["shape"] == "float32[8]"  # the triggering shape
    telemetry.clear_records()


def test_compile_count_latency_and_span(sentry):
    """Every compile (warmup included) lands in xla.compile.count, the
    latency histogram, and — inside a trace — as an xla.compile child
    span of the dispatching context."""
    import jax
    import jax.numpy as jnp

    count0 = telemetry.counters("xla.compile.count").get(
        "xla.compile.count", 0)
    with telemetry.span("outer.dispatch") as sp:
        jax.jit(lambda x: x + 3.0)(jnp.ones((3,), jnp.float32))
    if not sentry.listener_active:
        pytest.skip("jax.monitoring unavailable in this build")
    assert telemetry.counters("xla.compile.count")[
        "xla.compile.count"] > count0
    snap = telemetry.export_snapshot(include_spans=False)
    # labeled by the sentry's stage: xla.compile.latency{stage="..."}
    assert sum(h["count"] for k, h in snap["histograms"].items()
               if k.startswith("xla.compile.latency{")) > 0
    names = {r["name"] for r in telemetry.get_trace(sp.trace_id)}
    assert "xla.compile" in names


def test_warmup_compiles_not_flagged(sentry):
    import jax
    import jax.numpy as jnp

    with sentry.warmup():
        jax.jit(lambda x: x - 1.0)(jnp.ones((5,), jnp.float32))
        assert telemetry.counters("xla.compile.hot_path") == {}
    assert not sentry.in_warmup  # warmup() exit re-arms flagging
    sentry.reset()
    assert sentry.in_warmup


def test_watch_compiles_passes_through_jit_surface(sentry):
    """Call sites treat the wrapped value as a PjitFunction: .lower()
    (benchmarks/rehearse_aot.py does exactly this on
    make_lm_train_epoch's result) and attribute access must pass
    through."""
    import jax
    import jax.numpy as jnp

    f = telemetry.watch_compiles(jax.jit(lambda x: x * x), name="test.sq")
    lowered = f.lower(jnp.ones((2,), jnp.float32))
    compiled = lowered.compile()
    out = compiled(jnp.ones((2,), jnp.float32))
    assert np.allclose(np.asarray(out), 1.0)
    assert "test.sq" in repr(f)


# ------------------------------------------------------------- memory gauges
def test_sample_device_memory_graceful_on_cpu():
    """CPU backends return memory_stats()=None: the HBM gauges are
    skipped without error, the live-buffer count still lands."""
    import jax.numpy as jnp

    keep = jnp.ones((16,), jnp.float32) + 1.0  # a live committed buffer
    out = device_obs.sample_device_memory()
    assert isinstance(out, dict)
    assert out.get("live_buffer_count", 0) >= 1
    gauges = telemetry.export_snapshot(include_spans=False)["gauges"]
    assert gauges["device.live_buffer_count"] >= 1
    # HBM gauges appear only on memory_stats backends; on CPU they
    # must be absent rather than zero/garbage
    import jax
    has_stats = any(d.memory_stats() for d in jax.local_devices())
    assert ("hbm_bytes_in_use" in out) == has_stats
    del keep


def test_memory_sampler_thread():
    sampler = device_obs.start_memory_sampler(interval_s=0.01)
    try:
        time.sleep(0.08)
    finally:
        sampler.stop()
    assert "device.live_buffer_count" in telemetry.export_snapshot(
        include_spans=False)["gauges"]


def test_sample_passive_without_jax(monkeypatch):
    """A process that never imported jax must get {} — sampling can't be
    the thing that drags the runtime in."""
    monkeypatch.setattr(device_obs, "_jax_if_initialized", lambda: None)
    assert device_obs.sample_device_memory() == {}


# ------------------------------------------------------- chrome trace export
def test_render_chrome_trace_unit_roundtrip():
    telemetry.clear_spans()
    with telemetry.span("client.call") as root:
        with telemetry.span("server.handle"):
            with telemetry.span("batcher.run"):
                pass
    doc = telemetry.render_chrome_trace()
    text = json.dumps(doc)  # must serialize
    doc2 = json.loads(text)
    assert doc2["displayTimeUnit"] == "ms"
    evs = doc2["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"client.call", "server.handle",
                                      "batcher.run"}
    for e in xs:
        assert e["dur"] >= 0 and e["pid"] == os.getpid()
        assert isinstance(e["tid"], int)
        assert e["args"]["trace_id"] == root.trace_id
    by_name = {e["name"]: e for e in xs}
    # parent/child nesting is carried in args
    assert by_name["server.handle"]["args"]["parent_id"] == \
        by_name["client.call"]["args"]["span_id"]
    assert by_name["batcher.run"]["args"]["parent_id"] == \
        by_name["server.handle"]["args"]["span_id"]


def test_chrome_trace_attrs_hardened():
    """Satellite: a stray ndarray/dtype attr degrades to repr() in both
    export_snapshot and render_chrome_trace instead of crashing."""
    telemetry.clear_spans()
    with telemetry.span("weird.span", arr=np.zeros(3),
                        dt=np.dtype("float32"), ok=7):
        pass
    snap = telemetry.export_snapshot()
    json.dumps(snap)  # repr() fallback keeps the dump serializable
    rec = [s for s in snap["spans"] if s["name"] == "weird.span"][-1]
    assert rec["attrs"]["ok"] == 7
    assert isinstance(rec["attrs"]["arr"], str)
    doc = telemetry.render_chrome_trace()
    json.dumps(doc)
    ev = [e for e in doc["traceEvents"]
          if e.get("name") == "weird.span"][-1]
    assert isinstance(ev["args"]["arr"], str)
    assert ev["args"]["ok"] == 7
    telemetry.clear_spans()


# -------------------------------------------------------- snapshot meta block
def test_export_snapshot_meta():
    import jax  # noqa: F401 — ensures backend facts are reportable

    snap = telemetry.export_snapshot(timestamp="2026-08-05T12:00:00Z")
    meta = snap["meta"]
    assert meta["timestamp"] == "2026-08-05T12:00:00Z"
    assert meta["pid"] == os.getpid()
    assert meta["uptime_s"] >= 0
    assert meta["backend"] == "cpu"
    assert meta["device_count"] >= 1
    # timestamp is caller-passed, not invented
    assert telemetry.export_snapshot()["meta"]["timestamp"] is None


def test_obs_report_prints_meta_header():
    from tools import obs_report

    snap = telemetry.export_snapshot(timestamp="2026-08-05T12:00:00Z",
                                     include_spans=False)
    text = obs_report.render_report(snap)
    assert "== snapshot meta ==" in text
    assert "timestamp = 2026-08-05T12:00:00Z" in text
    assert f"pid = {os.getpid()}" in text


def test_obs_report_chrome_out(tmp_path):
    from tools import obs_report

    telemetry.clear_spans()
    with telemetry.span("report.span"):
        pass
    snap_file = tmp_path / "snap.json"
    snap_file.write_text(json.dumps(telemetry.export_snapshot()))
    chrome_file = tmp_path / "chrome.json"
    rc = obs_report.main([str(snap_file), "--chrome-out", str(chrome_file)])
    assert rc == 0
    doc = json.loads(chrome_file.read_text())
    assert any(e.get("name") == "report.span" for e in doc["traceEvents"])
    telemetry.clear_spans()


# ------------------------------------------- sanitize-collision metrics lint
def test_metrics_lint_fails_on_sanitize_collision(monkeypatch, capsys):
    from tools import ci

    monkeypatch.setattr(ci, "_declared_metric_names",
                        lambda: {"a.b.c", "a.b_c"})
    monkeypatch.setattr(ci, "_py_files", lambda: [])
    assert ci.metrics_lint() == 1
    assert "M002" in capsys.readouterr().out


def test_real_declared_metrics_have_no_collisions():
    from tools import ci

    names = ci._declared_metric_names()
    # covers the new xla.* / device.* names
    assert "xla.compile.hot_path" in names
    assert "device.hbm.bytes_in_use" in names
    sanitized = [ci._sanitize_metric_name(n) for n in names]
    assert len(set(sanitized)) == len(sanitized)


def test_ci_sanitizer_matches_exposition():
    """The lint's replicated sanitizer must stay in lockstep with the
    exposition's (the lint can't import mmlspark_tpu; parity pinned
    here)."""
    from tools import ci
    from mmlspark_tpu.core.telemetry.exposition import sanitize_name

    for name in ("a.b.c", "a-b/c", "9lives", "x{y}", "ok_name:x",
                 "serving.request.latency"):
        assert ci._sanitize_metric_name(name) == sanitize_name(name)


# ----------------------------------------- serving debug endpoints satellite
@pytest.fixture
def live_server():
    from mmlspark_tpu.core.pipeline import LambdaTransformer
    from mmlspark_tpu.io.feed import DeviceFeed
    from mmlspark_tpu.serving.server import ServingServer

    feed = DeviceFeed()

    def fn(table):
        v = np.asarray(table["v"], np.float32)
        dv = feed.put(v)
        return table.with_column("y", np.asarray(dv) * 2.0)

    srv = ServingServer(LambdaTransformer(fn), reply_col="y",
                        name="obs-dev", path="/score", input_schema=["v"])
    info = srv.start()
    try:
        yield info
    finally:
        srv.stop()


def test_unknown_trace_id_clean_404(live_server):
    base = live_server.url.rsplit("/", 1)[0]
    with pytest.raises(urllib.error.HTTPError) as exc_info:
        urllib.request.urlopen(base + "/trace/no-such-trace-id")
    err = exc_info.value
    assert err.code == 404
    assert json.loads(err.read().decode())["error"] == "unknown trace id"


def test_metrics_content_type_and_device_signals(live_server):
    import jax
    import jax.numpy as jnp

    telemetry.track_compiles()
    jax.jit(lambda x: x * 5.0)(jnp.ones((2,), jnp.float32))
    base = live_server.url.rsplit("/", 1)[0]
    with urllib.request.urlopen(base + "/metrics") as resp:
        ctype = resp.headers["Content-Type"]
        body = resp.read().decode("utf-8")
    assert ctype.startswith("text/plain; version=0.0.4")
    # the new signals on a live server's scrape
    assert "device_live_buffer_count" in body
    assert "xla_compile_count" in body
    assert "xla_compile_latency_count" in body


def test_trace_json_live_roundtrip_nesting(live_server):
    """Acceptance: a live client→server→batcher trace renders as valid
    trace-event JSON with correct parent/child nesting and non-negative
    durations."""
    from mmlspark_tpu.io.http.clients import send_request
    from mmlspark_tpu.io.http.schema import to_http_request

    telemetry.clear_spans()
    resp = send_request(to_http_request(
        live_server.url, {"v": 3.0},
        headers={"X-Trace-Id": "chromeacceptance1"}))
    assert resp.status_code == 200
    base = live_server.url.rsplit("/", 1)[0]
    with urllib.request.urlopen(base + "/trace.json") as r:
        doc = json.loads(r.read().decode("utf-8"))  # round-trips
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["dur"] >= 0 for e in xs)
    ours = [e for e in xs if e["args"]["trace_id"] == "chromeacceptance1"]
    names = {e["name"] for e in ours}
    assert "serving.request" in names
    by_id = {e["args"]["span_id"]: e for e in ours}
    request_ev = next(e for e in ours if e["name"] == "serving.request")
    # batcher/feed children hang off the request span's subtree
    children = [e for e in ours
                if e["args"]["parent_id"] in by_id
                and e["args"]["span_id"] != request_ev["args"]["span_id"]]
    assert children, "request must have linked child events"
    assert any(e["name"].startswith(("serving.batcher", "feed."))
               for e in children)
    telemetry.clear_spans()
