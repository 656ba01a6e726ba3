"""The continuous batcher on the profiler's clock: phase annotations under
a `jax.profiler` capture, the loop's always-on timers and counters, the
request's wait -> prefill -> decode tree, and the compile sentry's trace
and lower totals."""
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.core.telemetry import device as device_mod
from mmlspark_tpu.models.generation import generate
from mmlspark_tpu.models.transformer import transformer_lm
from mmlspark_tpu.serving import batcher as batcher_mod
from mmlspark_tpu.serving.batcher import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TICK_PHASES = ("intake", "admit", "grow", "upload", "dispatch", "fetch",
               "emit")
ADMIT_PHASES = ("pack", "prefill", "first_token")
HISTS = ("serving.batcher.tick.latency", "serving.batcher.tick.host",
         "serving.batcher.admit.latency", "serving.batcher.queue_wait")
COUNTERS = ("serving.batcher.prefill.tokens",
            "serving.batcher.prefill.padded_tokens",
            "serving.batcher.live_tokens",
            batcher_mod.TICK_OVERLAPPED, batcher_mod.TICK_LATE_DISCARDS)

# two waves, each submitted whole before the tick that admits it:
# (prompt length, max_new_tokens)
WAVES = ([(3, 4), (5, 3), (20, 2), (18, 6)], [(2, 5), (17, 1), (9, 3)])


@pytest.fixture(scope="module")
def lm():
    model = transformer_lm(vocab_size=64, embed_dim=32, num_layers=2,
                           num_heads=2, max_len=48, dtype=jnp.float32)
    variables = model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 4), jnp.int32), train=False)
    return model, {c: v for c, v in variables.items() if c != "kvcache"}


def _reference(model, variables, prompt, n):
    out = generate(model, variables, jnp.asarray(prompt)[None],
                   max_new_tokens=n)
    return np.asarray(out)[0, len(prompt):].tolist()


def _prompt(rng, n):
    return rng.integers(1, 64, size=n).tolist()


def _hist(name):
    """count and sum over every label set of `name`"""
    snaps = [h.snapshot() for (n, _), h in
             telemetry.REGISTRY.histograms().items() if n == name]
    return (sum(s["count"] for s in snaps), sum(s["sum"] for s in snaps))


def _bucket(n):
    b = 16
    while b < n:
        b *= 2
    return b


def _rows(k, slots):
    kp = 1
    while kp < k:
        kp *= 2
    return min(kp, slots)


@pytest.fixture(scope="module")
def scripted(lm):
    """Both waves through a paged batcher whose loop this thread drives
    by hand (`_tick()` until every stream closed), so that which requests
    share an admission is the script's and every count is exact."""
    model, variables = lm
    rng = np.random.default_rng(7)
    before = {n: _hist(n) for n in HISTS}
    counters0 = {n: telemetry.counters().get(n, 0) for n in COUNTERS}
    seen = {n: [] for n in HISTS[:2]}
    hists = {n: telemetry.histogram(n) for n in seen}
    for n, h in hists.items():
        # the registry hands every caller this object: record what it is
        # given, observation by observation, beside what it keeps
        h.observe = (lambda v, n=n, real=type(h).observe, h=h:
                     (seen[n].append(v), real(h, v))[1])
    telemetry.clear_spans()
    batcher = ContinuousBatcher(model, variables, max_slots=4, paged=True,
                                page_size=8)
    waves, ticks, trace_ids = [], 0, []
    try:
        for wave in WAVES:
            reqs = []
            for n, m in wave:
                prompt = _prompt(rng, n)
                with telemetry.span("serving.request") as sp:
                    stream = batcher.submit(prompt, max_new_tokens=m)
                trace_ids.append(sp.trace_id)
                reqs.append((prompt, m, stream))
            while any(r is not None for r in batcher._live) or \
                    batcher._intake.depth():
                fills = _hist("serving.batcher.batch_fill")[0]
                batcher._tick()
                ticks += _hist("serving.batcher.batch_fill")[0] - fills
            waves.append([(p, m, s.tokens()) for p, m, s in reqs])
    finally:
        batcher.stop()
        for h in hists.values():
            del h.observe
    return {"waves": waves, "ticks": ticks, "seen": seen,
            "trace_ids": trace_ids,
            "hists": {n: (_hist(n)[0] - before[n][0],
                          _hist(n)[1] - before[n][1]) for n in HISTS},
            "counters": {n: telemetry.counters().get(n, 0) - counters0[n]
                         for n in COUNTERS}}


def test_scripted_outputs_are_generates(lm, scripted):
    model, variables = lm
    for wave in scripted["waves"]:
        for prompt, m, toks in wave:
            assert toks == _reference(model, variables, prompt, m)


# a request decodes m - 1 times after its admission's first token, and a
# wave admitted whole runs as long as its longest reply
STEPS = sum(max(m for _n, m in w) - 1 for w in WAVES)


@pytest.mark.parametrize("name,expected", [
    # the loop runs one step ahead: the iteration that only fetches a
    # wave's last tokens dispatches no step and observes neither timer
    ("serving.batcher.tick.latency", STEPS),
    ("serving.batcher.tick.host", STEPS),
    ("serving.batcher.admit.latency", len(WAVES)),
    ("serving.batcher.queue_wait", sum(len(w) for w in WAVES)),
])
def test_timer_counts_are_what_the_run_implies(scripted, name, expected):
    count, total = scripted["hists"][name]
    assert count == expected
    assert total > 0
    if name.startswith("serving.batcher.tick."):
        assert count == scripted["ticks"] == len(scripted["seen"][name])


def test_tick_host_is_within_tick_latency_each_time(scripted):
    latency = scripted["seen"]["serving.batcher.tick.latency"]
    host = scripted["seen"]["serving.batcher.tick.host"]
    assert len(host) == len(latency) > 0
    assert all(0 <= h <= t for h, t in zip(host, latency))


@pytest.mark.parametrize("name,expected", [
    ("serving.batcher.prefill.tokens",
     sum(n for w in WAVES for n, _m in w)),
    # per wave and bucket: rows padded to a power of two, times the bucket
    ("serving.batcher.prefill.padded_tokens",
     sum(_rows(sum(1 for n, _m in w if _bucket(n) == b), 4) * b
         for w in WAVES for b in {_bucket(n) for n, _m in w})),
    # a request sits at positions n .. n + m - 2 over its m - 1 decode ticks
    ("serving.batcher.live_tokens",
     sum(n + j for w in WAVES for n, m in w for j in range(m - 1))),
    # every step but a wave's first is dispatched with the step before it
    # still unfetched; no reply here ends by an eos
    (batcher_mod.TICK_OVERLAPPED, STEPS - len(WAVES)),
    (batcher_mod.TICK_LATE_DISCARDS, 0),
])
def test_counters_are_exact(scripted, name, expected):
    assert scripted["counters"][name] == expected


def test_request_tree_is_wait_prefill_decode(scripted):
    flat = [r for w in scripted["waves"] for r in w]
    for tid, (prompt, m, toks) in zip(scripted["trace_ids"], flat):
        (root,) = telemetry.span_tree(tid)
        assert root["name"] == "serving.request"
        kids = root["children"]
        assert [k["name"] for k in kids] == [
            "serving.batcher.wait", "serving.batcher.prefill",
            "serving.batcher.decode"]
        wait, prefill, decode = kids
        assert prefill["attrs"]["bucket"] == _bucket(len(prompt))
        assert prefill["attrs"]["slot"] == wait["attrs"]["slot"] \
            == decode["attrs"]["slot"]
        assert prefill["attrs"]["rows"] in (1, 2, 4)
        assert decode["attrs"]["tokens"] == len(toks) == m
    assert not [s for s in telemetry.recent_spans()
                if s["name"] == "serving.batcher.admit"]


@pytest.fixture(scope="module")
def captured(lm, tmp_path_factory, bench_trace_lib):
    """A started batcher serving a few requests under a profiler capture
    taken with the options benchmarks/run.py captures with; the host
    plane as the reducers read it."""
    model, variables = lm
    rng = np.random.default_rng(11)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    batcher = ContinuousBatcher(model, variables, max_slots=2, paged=True,
                                page_size=8)
    # warm every program first, so that the capture holds ticks and not
    # compiles
    batcher.start()
    try:
        for s in [batcher.submit(_prompt(rng, n), max_new_tokens=3)
                  for n in (4, 20)]:
            s.tokens()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            streams = [batcher.submit(_prompt(rng, n), max_new_tokens=m)
                       for n, m in ((3, 5), (19, 4), (6, 6))]
            got = [s.tokens() for s in streams]
            time.sleep(0.05)          # the loop, idle again, under capture
        finally:
            jax.profiler.stop_trace()
    finally:
        batcher.stop()
    assert [len(g) for g in got] == [5, 4, 6]
    tr = bench_trace_lib
    path = tr.find_xplane(log_dir)
    # the reducers' reader names every Python thread's line alike, so the
    # thread is the line's place in the host plane, read beside it
    spans = [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
              i)
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for i, line in enumerate(plane.lines) for e in line.events
             if e.name.startswith("serving.batcher.")]
    read = sorted((n, round(s, 9)) for n, s, _d, _t in
                  tr.load_xplane(path).host
                  if n.startswith("serving.batcher."))
    assert read == sorted((n, round(s, 9)) for n, s, _e, _t in spans)
    return spans


def _inside(inner, outers, slack=2e-6):
    _n, s, e, thread = inner
    return any(os_ - slack <= s and e <= oe + slack and ot == thread
               for _on, os_, oe, ot in outers)


def test_capture_holds_the_tick_and_the_idle_wait(captured):
    names = {n for n, _s, _e, _t in captured}
    assert batcher_mod.TICK in names
    assert batcher_mod.IDLE in names       # the wait before the submits
    ticks = [x for x in captured if x[0] == batcher_mod.TICK]
    assert len({t for _n, _s, _e, t in ticks}) == 1      # the loop thread
    idles = [x for x in captured if x[0] == batcher_mod.IDLE]
    assert not any(_inside(i, ticks) for i in idles)


@pytest.mark.parametrize("phase", TICK_PHASES)
def test_capture_nests_each_phase_in_a_tick(captured, phase):
    ticks = [x for x in captured if x[0] == batcher_mod.TICK]
    mine = [x for x in captured if x[0] == f"{batcher_mod.TICK}.{phase}"]
    assert mine, sorted({n for n, _s, _e, _t in captured})
    assert all(_inside(x, ticks) for x in mine)


@pytest.mark.parametrize("phase", ADMIT_PHASES)
def test_capture_nests_admission_phases_in_tick_admit(captured, phase):
    admits = [x for x in captured if x[0] == batcher_mod.TICK_ADMIT]
    mine = [x for x in captured
            if x[0] == f"serving.batcher.admit.{phase}"]
    assert mine and admits
    if phase == "pack":
        # several buckets pack on a flow worker: inside the admission in
        # time, on that worker's thread
        admits = [(n, s, e, x[3]) for x in mine for n, s, e, _t in admits]
    assert all(_inside(x, admits) for x in mine)


def test_telemetry_without_jax_imports_nothing():
    code = (
        "import sys\n"
        "from mmlspark_tpu.core import telemetry\n"
        "h = telemetry.histogram('serving.batcher.tick.latency')\n"
        "with telemetry.span('a.b') as sp:\n"
        "    with telemetry.phase('a.b.c', h) as ph:\n"
        "        pass\n"
        "with telemetry.device_annotation('feed.transfer'):\n"
        "    pass\n"
        "assert h.snapshot()['count'] == 1 and ph.elapsed_s >= 0\n"
        "assert telemetry.get_trace(sp.trace_id)[0]['name'] == 'a.b'\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]\n"
        "assert not bad, bad\n"
        "for gone in ('enable_device_annotations', 'set_annotation_hook',\n"
        "             'get_annotation_hook', 'DEFAULT_ANNOTATION_PREFIXES'):\n"
        "    assert not hasattr(telemetry, gone), gone\n"
        "print('ok')\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip() == "ok"


def test_sentry_keeps_trace_and_lower_of_a_new_function():
    telemetry.track_compiles()
    names = ("xla.compile.trace.latency", "xla.compile.lower.latency",
             "xla.compile.latency")
    x = jnp.arange(5)
    before = {n: _hist(n) for n in names}
    # primitives only: `x * x` would trace the jitted jnp.multiply inside
    out = jax.jit(lambda x: jax.lax.add(jax.lax.mul(x, x), x))(x)
    assert out.tolist() == [0, 2, 6, 12, 20]
    for n in names:
        count, total = _hist(n)
        assert count - before[n][0] == 1, n
        assert total >= before[n][1]


def test_sentry_trace_time_is_self_time(monkeypatch):
    """An outer trace's event arrives after, and encloses, the events of
    the functions traced inside it: it records what is left."""
    sentry = device_mod.CompileSentry()
    clock = iter([10.5, 10.9, 11.0, 12.0])
    monkeypatch.setattr(device_mod, "time", types.SimpleNamespace(
        time=lambda: next(clock)))
    assert sentry._trace_self_time(0.3) == pytest.approx(0.3)  # 10.2 - 10.5
    assert sentry._trace_self_time(0.2) == pytest.approx(0.2)  # 10.7 - 10.9
    assert sentry._trace_self_time(1.0) == pytest.approx(0.5)  # 10.0 - 11.0
    assert sentry._trace_self_time(0.5) == pytest.approx(0.5)  # a sibling
