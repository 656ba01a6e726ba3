"""The compile sentry's account of set-up: the stage it latches, each
trace, lower and compile-or-fetch under the function's name and the
stage, the persistent cache's answer to every compile, the bounded
per-program table and the start-up report.  Synthetic events drive fresh
sentries (the process-wide one is shared with every other test); one
real `jax.jit` runs under a temporary persistent cache."""
import types

import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.core.telemetry import device as device_mod

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
WRITTEN = "/jax/compilation_cache/cache_misses"


@pytest.fixture
def sentry(monkeypatch):
    """A fresh, uninstalled sentry whose trace events never nest: the
    clock moves 10 s between two of them."""
    clock = iter(range(10**6, 10**9, 10))
    monkeypatch.setattr(device_mod, "time", types.SimpleNamespace(
        time=lambda: float(next(clock))))
    monkeypatch.setattr(device_mod, "_persistent_cache_on", lambda: True)
    return device_mod.CompileSentry()


def compile_once(s, name, answer=None, trace=0.25, lower=0.5, seconds=1.0):
    """One program's events in JAX's order: traced as `name`, lowered and
    compiled as `jit(name)`, the cache's answer just before the compile."""
    s._on_event_duration(TRACE, trace, fun_name=name)
    s._on_event_duration(LOWER, lower, fun_name=f"jit({name})")
    if answer is not None:
        s._on_event(answer)
    s._on_event_duration(COMPILE, seconds, fun_name=f"jit({name})")


def hist(name, stage):
    h = telemetry.REGISTRY.histograms().get((name, (("stage", stage),)))
    if h is None:
        return 0, 0.0
    snap = h.snapshot()
    return snap["count"], snap["sum"]


def counter(name):
    return telemetry.counters().get(name, 0)


def setup_records():
    return [r for r in telemetry.recent_records()
            if r.get("method") == "setup_programs"]


def test_reset_after_end_warmup_stays_in_run(sentry):
    telemetry.clear_records()
    assert sentry.stage == "setup" and sentry.in_warmup
    sentry.end_warmup()
    assert sentry.stage == "run" and not sentry.in_warmup
    sentry.reset()
    assert sentry.in_warmup and sentry.stage == "run"
    with sentry.warmup():
        assert sentry.stage == "run"
    sentry.end_warmup()
    assert sentry.stage == "run"
    # set-up ended once, so it was reported once
    assert len(setup_records()) == 1
    telemetry.clear_records()


def test_events_land_under_the_function_and_the_stage(sentry):
    names = ("xla.compile.trace.latency", "xla.compile.lower.latency",
             "xla.compile.latency")
    before = {(n, s): hist(n, s) for n in names for s in ("setup", "run")}
    compile_once(sentry, "alpha", trace=0.25, lower=0.5, seconds=2.0)
    sentry._on_event_duration(TRACE, 0.125, fun_name="alpha")
    sentry.end_warmup()
    compile_once(sentry, "alpha", trace=0.0625, lower=0.25, seconds=4.0)
    setup = sentry.programs("setup")
    assert setup == {"alpha": {"traced": 2, "trace_s": 0.375, "lower_s": 0.5,
                               "compile_s": 2.0, "hits": 0, "misses": 1,
                               "unwritten": 1}}
    assert sentry.programs("run")["alpha"]["compile_s"] == 4.0
    assert sentry.totals()["compile_s"] == 6.0
    for (n, s), want in {
            (names[0], "setup"): (2, 0.375), (names[1], "setup"): (1, 0.5),
            (names[2], "setup"): (1, 2.0), (names[0], "run"): (1, 0.0625),
            (names[1], "run"): (1, 0.25), (names[2], "run"): (1, 4.0)}.items():
        count, total = hist(n, s)
        assert count - before[n, s][0] == want[0], (n, s)
        assert total - before[n, s][1] == pytest.approx(want[1]), (n, s)


@pytest.mark.parametrize("answer,cache_on,classed", [
    (HIT, True, "hits"),
    (WRITTEN, True, "misses"),
    (None, True, "unwritten"),
    (None, False, "misses"),
], ids=["hit", "miss-written", "miss-under-threshold", "cache-off"])
def test_the_cache_answer_is_classed(sentry, monkeypatch, answer, cache_on,
                                     classed):
    monkeypatch.setattr(device_mod, "_persistent_cache_on", lambda: cache_on)
    kinds = ("hits", "misses", "unwritten")
    before = {k: counter(f"xla.compile.cache.{k}.setup") for k in kinds}
    compile_once(sentry, "beta", answer=answer)
    # the answer belongs to one compile: the next one starts unanswered
    sentry._on_event_duration(COMPILE, 1.0, fun_name="jit(gamma)")
    row = sentry.programs("setup")["beta"]
    got = {k: row[k] for k in kinds}
    want = {"hits": int(classed == "hits"),
            "misses": int(classed != "hits"),
            "unwritten": int(classed == "unwritten")}
    assert got == want
    assert {k: counter(f"xla.compile.cache.{k}.setup") - before[k]
            for k in kinds} == {"hits": want["hits"],
                                "misses": want["misses"] + 1,
                                "unwritten": want["unwritten"] + cache_on}


def test_the_table_folds_past_its_names(sentry):
    n = device_mod.PROGRAMS_KEPT + 44
    for i in range(n):
        sentry._on_event_duration(COMPILE, 0.5, fun_name=f"jit(f{i})")
    rows = sentry.programs("setup")
    assert len(rows) == device_mod.PROGRAMS_KEPT + 1
    assert rows[device_mod.OTHER_PROGRAMS]["misses"] == 44
    assert rows[device_mod.OTHER_PROGRAMS]["compile_s"] == 22.0
    assert "f0" in rows and f"f{n - 1}" not in rows
    assert sentry.totals("setup")["misses"] == n


def test_the_setup_report_names_the_costliest(sentry):
    telemetry.clear_records()
    for i in range(25):
        compile_once(sentry, f"p{i}", answer=HIT, seconds=float(i))
    sentry.end_warmup()
    compile_once(sentry, "late", seconds=100.0)
    (rec,) = setup_records()
    assert rec["names"] == 25
    assert [p["name"] for p in rec["programs"]] == [
        f"p{i}" for i in range(24, 4, -1)]
    assert rec["totals"]["hits"] == 25 and rec["totals"]["misses"] == 0
    assert rec["totals"]["compile_s"] == pytest.approx(sum(range(25)))
    telemetry.clear_records()


def test_install_records_where_setup_started():
    from jax import monitoring

    s = device_mod.CompileSentry()
    s.install()
    try:
        age = device_mod.process_age_s()
        assert 0.0 < s.report()["start_s"] <= age
        assert telemetry.REGISTRY.gauge_values()["setup.start_s"] == \
            s.report()["start_s"]
        assert s.listener_active
    finally:
        monitoring.unregister_event_duration_listener(s._on_event_duration)
        monitoring.unregister_event_listener(s._on_event)


def test_a_real_compile_under_a_persistent_cache(tmp_path):
    """At a write threshold above its compile time the call is a miss that
    is not kept; at threshold 0 the next compile is a miss that is kept;
    the one after that is a hit."""
    import jax
    import jax.numpy as jnp
    from jax._src import compilation_cache

    sentry = telemetry.track_compiles()
    x = jnp.arange(7.0)

    def probe_setup_trace(v):
        return jax.lax.mul(jax.lax.sin(v), v) + 3.0

    def row():
        rows = [sentry.programs(s).get("probe_setup_trace")
                for s in ("setup", "run")]
        return {k: sum(r[k] for r in rows if r) for k in
                ("traced", "hits", "misses", "unwritten")}

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    seen = []
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        for threshold in (3600.0, 0.0, 0.0):
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              threshold)
            jax.clear_caches()
            compilation_cache.reset_cache()
            out = jax.jit(probe_setup_trace)(x)
            seen.append(row())
        assert float(out[0]) == 3.0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        jax.clear_caches()
        compilation_cache.reset_cache()
    first, second, third = seen
    assert first == {"traced": 1, "hits": 0, "misses": 1, "unwritten": 1}
    assert second == {"traced": 2, "hits": 0, "misses": 2, "unwritten": 1}
    assert third == {"traced": 3, "hits": 1, "misses": 2, "unwritten": 1}
