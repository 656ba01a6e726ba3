"""Chaos harness tests: seeded fault injection and every recovery path
it drives (PR 4, docs/robustness.md).

Everything here is deterministic — `chaos` means reproducible faults,
not flakiness: the injector draws per-point from `Random(f"{seed}:
{point}")`, so a failing run reproduces with its seed.
"""
import importlib.util
import json
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from mmlspark_tpu.core import telemetry
from mmlspark_tpu.io.http.clients import (CircuitBreaker, HandlingUtils,
                                          send_request)
from mmlspark_tpu.io.http.schema import HTTPRequestData, to_http_request
from mmlspark_tpu.serving.server import WorkerServer
from mmlspark_tpu.utils.fault_tolerance import (Overloaded,
                                                retry_with_backoff,
                                                retry_with_timeout)
from mmlspark_tpu.utils.faults import (FAULTS, FaultPlan, InjectedCrash,
                                       InjectedFault, fault_point)


def _counter(name):
    return telemetry.counters().get(name, 0)


# ------------------------------------------------------- the injector

@pytest.mark.chaos
def test_injector_schedule_is_seed_deterministic():
    def schedule(seed):
        fired = []
        with FAULTS.arm(FaultPlan(seed=seed).on("p", probability=0.3)):
            for i in range(200):
                try:
                    fault_point("p")
                except InjectedFault:
                    fired.append(i)
        return fired

    a, b = schedule(11), schedule(11)
    assert a == b and len(a) > 0          # same seed, same schedule
    assert schedule(12) != a              # different seed, different one


@pytest.mark.chaos
def test_nth_max_failures_latency_and_disarmed_noop():
    plan = (FaultPlan(seed=0)
            .on("exact", nth=[0, 2])
            .on("budget", probability=1.0, max_failures=2)
            .on("slow", nth=[0], latency_s=0.05, error=None))
    with FAULTS.arm(plan):
        outcomes = []
        for _ in range(4):
            try:
                fault_point("exact")
                outcomes.append("ok")
            except InjectedFault:
                outcomes.append("boom")
        assert outcomes == ["boom", "ok", "boom", "ok"]
        for i in range(5):  # budget: only the first two fire
            if i < 2:
                with pytest.raises(InjectedFault):
                    fault_point("budget")
            else:
                fault_point("budget")
        t0 = time.monotonic()
        fault_point("slow")               # latency-only: no raise
        assert time.monotonic() - t0 >= 0.04
        assert FAULTS.fires == {"exact": 2, "budget": 2, "slow": 1}
        assert FAULTS.calls["exact"] == 4
    # disarmed: a point costs nothing and never raises
    fault_point("exact")


@pytest.mark.chaos
def test_arm_is_non_reentrant_and_crash_escapes_except_exception():
    with FAULTS.arm(FaultPlan(seed=0).on("c", nth=[0],
                                         error=InjectedCrash)):
        with pytest.raises(RuntimeError, match="already armed"):
            with FAULTS.arm(FaultPlan(seed=1)):
                pass
        with pytest.raises(InjectedCrash):
            try:
                fault_point("c")
            except Exception:  # noqa: BLE001 — the point of the test
                pytest.fail("InjectedCrash must escape except Exception")
    assert _counter("faults.injected") >= 1


# ---------------------------------------------- fault_tolerance utils

def test_retry_with_timeout_rejects_nonpositive_retries():
    with pytest.raises(ValueError, match="retries"):
        retry_with_timeout(lambda: 1, timeout_sec=1.0, retries=0)


def test_retry_with_timeout_retryable_filter():
    calls = []

    def flaky():
        calls.append(1)
        raise KeyError("not retryable here")

    with pytest.raises(KeyError):
        retry_with_timeout(flaky, timeout_sec=1.0, retries=3,
                           retryable=(ValueError,))
    assert len(calls) == 1                # non-matching: no retries burned


def test_retry_with_backoff_full_jitter_and_on_retry():
    import random

    seen = []
    attempts = []

    def fails_twice():
        attempts.append(1)
        if len(attempts) < 3:
            raise ValueError("flaky")
        return "ok"

    out = retry_with_backoff(
        fails_twice, retries=5, initial_delay_sec=0.001,
        max_delay_sec=0.002, rng=random.Random(3),
        on_retry=lambda a, e, s: seen.append((a, type(e).__name__, s)))
    assert out == "ok" and len(attempts) == 3
    assert [(a, n) for a, n, _ in seen] == [(0, "ValueError"),
                                            (1, "ValueError")]
    for _a, _n, sleep_s in seen:          # full jitter: within [0, delay]
        assert 0.0 <= sleep_s <= 0.002


def test_retry_with_backoff_respects_retryable():
    with pytest.raises(KeyError):
        retry_with_backoff(lambda: (_ for _ in ()).throw(KeyError("x")),
                           retries=5, retryable=(ValueError,))


# ------------------------------------------------- feed retry/degrade

@pytest.mark.chaos
def test_feed_retries_then_degrades_to_unpipelined():
    from mmlspark_tpu.io.feed import DeviceFeed

    retry0 = _counter("feed.transfer_retry")
    deg0 = _counter("feed.degraded")
    feed = DeviceFeed()
    a = np.arange(8, dtype=np.float32)
    b = np.arange(6, dtype=np.int32)
    plan = FaultPlan(seed=5).on("feed.device_put", probability=1.0,
                                max_failures=4)
    with pytest.warns(RuntimeWarning, match="degraded"):
        with FAULTS.arm(plan):
            da, db = feed.put_group([a, b])
    assert feed.degraded                      # sticky: stays unpipelined
    np.testing.assert_array_equal(np.asarray(da), a)
    np.testing.assert_array_equal(np.asarray(db), b)
    assert _counter("feed.transfer_retry") > retry0
    assert _counter("feed.degraded") == deg0 + 1
    # degraded feed still serves correct per-array transfers
    dc, dd = feed.put_group([a * 2, b * 2])
    np.testing.assert_array_equal(np.asarray(dc), a * 2)
    np.testing.assert_array_equal(np.asarray(dd), b * 2)


# -------------------------------------------- serving shed + deadline

def _post_into(url, payload, results, i, headers=None):
    try:
        results[i] = send_request(to_http_request(url, payload,
                                                  headers=headers),
                                  timeout=15)
    except Exception as e:  # noqa: BLE001
        results[i] = e


@pytest.mark.chaos
def test_worker_server_sheds_503_with_retry_after():
    shed0 = _counter("serving.shed")
    ws = WorkerServer("shed", path="/s", max_queue=2)
    ws.start()
    try:
        url = ws.service_info.url
        results = [None] * 3
        threads = [threading.Thread(target=_post_into, daemon=True,
                                    args=(url, {"v": i}, results, i))
                   for i in range(2)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5
        while ws.queue.qsize() < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ws.queue.qsize() == 2
        _post_into(url, {"v": 99}, results, 2)   # over the bound: shed
        assert results[2].status_code == 503
        assert results[2].headers.get("Retry-After") is not None
        assert _counter("serving.shed") == shed0 + 1
        # the two accepted requests are still answerable
        _epoch, batch = ws.get_epoch_batch(4, 2000)
        while len(batch) < 2 and time.monotonic() < deadline:
            _e, more = ws.get_epoch_batch(4, 500)
            batch.extend(more)
        from mmlspark_tpu.io.http.schema import HTTPResponseData
        for req in batch:
            ws.reply_to(req.id, HTTPResponseData(200, "OK", {}, b"{}"))
        ws.commit(ws.epoch)
        for t in threads:
            t.join(timeout=5)
        assert all(r is not None and r.status_code == 200
                   for r in results[:2])
    finally:
        ws.stop()


@pytest.mark.chaos
def test_expired_deadline_fails_fast_with_504():
    exp0 = _counter("serving.deadline_expired")
    ws = WorkerServer("deadline", path="/d")
    ws.start()
    try:
        url = ws.service_info.url
        results = [None]
        t = threading.Thread(target=_post_into, daemon=True,
                             args=(url, {"v": 1}, results, 0),
                             kwargs={"headers": {"X-Deadline-Ms": "30"}})
        t.start()
        deadline = time.monotonic() + 5
        while ws.queue.qsize() < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.08)                   # let the deadline lapse
        _epoch, batch = ws.get_epoch_batch(4, 100)
        assert batch == []                 # never admitted to compute
        t.join(timeout=5)
        assert results[0].status_code == 504
        assert _counter("serving.deadline_expired") == exp0 + 1
    finally:
        ws.stop()


# ---------------------------------------------------- circuit breaker

def test_circuit_breaker_state_machine():
    clock = [0.0]
    br = CircuitBreaker("svc", failure_threshold=2, reset_timeout_s=10.0,
                        clock=lambda: clock[0])
    assert br.allow() and br.state == "closed"
    br.record(False)
    assert br.state == "closed"            # consecutive count not yet met
    br.record(True)
    br.record(False)
    assert br.state == "closed"            # success reset the streak
    br.record(False)
    br.record(False)
    assert br.state == "open" and not br.allow()
    assert br.retry_after_s() == pytest.approx(10.0)
    clock[0] = 10.5
    assert br.allow() and br.state == "half_open"
    assert not br.allow()                  # single probe slot
    br.record(False)                       # probe failed: re-open
    assert br.state == "open"
    clock[0] = 21.0
    assert br.allow()
    br.record(True)                        # probe succeeded: closed
    assert br.state == "closed" and br.allow()


@pytest.mark.chaos
def test_open_circuit_short_circuits_without_network():
    plan = FaultPlan(seed=1).on("http.send", probability=1.0)
    br = CircuitBreaker("down-host", failure_threshold=2,
                        reset_timeout_s=60.0)
    req = HTTPRequestData(url="http://127.0.0.1:1/x", method="GET",
                          headers={})
    with FAULTS.arm(plan):
        resp = HandlingUtils.advanced(req, backoffs_ms=(1,), timeout=1.0,
                                      breaker=br)
        assert resp.status_code in (0, 503)
        assert br.state == "open"          # two injected transport fails
        calls_before = FAULTS.calls["http.send"]
        resp2 = HandlingUtils.advanced(req, backoffs_ms=(1,), timeout=1.0,
                                       breaker=br)
        assert resp2.status_code == 503
        assert resp2.headers.get("X-Circuit") == "down-host"
        assert resp2.headers.get("Retry-After") is not None
        # short-circuit means NO attempt crossed the wire (or the point)
        assert FAULTS.calls["http.send"] == calls_before
    assert _counter("circuit.open.down-host") >= 1


def test_get_breaker_is_shared_per_name():
    from mmlspark_tpu.io.http.clients import get_breaker

    a = get_breaker("chaos-test-host", failure_threshold=3)
    b = get_breaker("chaos-test-host", failure_threshold=99)
    assert a is b and a.failure_threshold == 3


# --------------------------------------------------- batcher intake

def _fake_lm():
    import jax.numpy as jnp

    # what the batcher reads of a model: sizes, and the state a layer
    # keeps (one whole-context kind, a K and a V row of 4)
    return SimpleNamespace(max_len=16, kv_heads=1, head_dim=4, embed_dim=4,
                           num_heads=1, num_layers=1, dtype=jnp.float32,
                           vocab_size=8, moe_experts=0, moe_capacity=0,
                           cache_kinds=(("full", None),), layer_kinds=(0,),
                           cache_rows=((4, 4),))


@pytest.mark.chaos
def test_batcher_bounded_intake_sheds_overloaded():
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    shed0 = _counter("batcher.shed")
    cb = ContinuousBatcher(_fake_lm(), {"params": {}}, max_slots=2,
                           max_pending=1)
    cb.submit([1, 2], max_new_tokens=2)
    with pytest.raises(Overloaded):
        cb.submit([3, 4], max_new_tokens=2)
    assert _counter("batcher.shed") == shed0 + 1
    cb.stop()


@pytest.mark.chaos
def test_batcher_drops_expired_deadline_before_prefill():
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    exp0 = _counter("batcher.deadline_expired")
    cb = ContinuousBatcher(_fake_lm(), {"params": {}}, max_slots=2)
    stream = cb.submit([1, 2], max_new_tokens=2,
                       deadline=time.monotonic() - 0.1)
    # drive the loop's intake/admission inline (no loop thread): the
    # expired request must be failed fast, never reaching a prefill
    # (a prefill on the fake model would blow up — that's the proof)
    cb._drain_intake()
    cb._try_admit()
    with pytest.raises(TimeoutError, match="deadline"):
        list(stream)
    assert _counter("batcher.deadline_expired") == exp0 + 1
    cb.stop()


# -------------------------------------------- kill-and-resume training

@pytest.mark.chaos
def test_training_kill_and_resume_is_bit_exact(tmp_path):
    import flax.linen as nn
    import optax

    from mmlspark_tpu.models.training import (fit_epochs_resumable,
                                              init_train_state,
                                              make_train_step)
    from mmlspark_tpu.parallel.mesh import default_mesh

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(4)(x), {}

    model, opt = M(), optax.sgd(0.1)
    mesh = default_mesh()
    gen = np.random.default_rng(0)
    imgs = gen.normal(size=(64, 4, 4, 1)).astype(np.float32)
    lbls = gen.integers(0, 4, size=64)
    step = make_train_step(model, opt, 4, mesh=mesh, donate=False)

    def fresh():
        return init_train_state(model, opt, (4, 4, 1), seed=0)

    kw = dict(batch_size=16, epochs=3, checkpoint_every=4, mesh=mesh,
              seed=7)
    ref, _ = fit_epochs_resumable(step, fresh(), imgs, lbls,
                                  checkpoint_dir=str(tmp_path / "ref"),
                                  **kw)
    # killed at global step 6 (an un-checkpointed step mid-epoch 1)...
    crash = FaultPlan(seed=1).on("training.step", nth=[6],
                                 error=InjectedCrash)
    with pytest.raises(InjectedCrash):
        with FAULTS.arm(crash):
            fit_epochs_resumable(step, fresh(), imgs, lbls,
                                 checkpoint_dir=str(tmp_path / "kill"),
                                 **kw)
    # ...and resumed from the auto-checkpoint: bit-for-bit identical
    res0 = _counter("training.resume")
    res, _ = fit_epochs_resumable(step, fresh(), imgs, lbls,
                                  checkpoint_dir=str(tmp_path / "kill"),
                                  **kw)
    assert _counter("training.resume") == res0 + 1
    assert int(ref.step) == int(res.step) == 12
    import jax

    mismatches = [
        p for p, (x, y) in enumerate(zip(jax.tree.leaves(ref.params),
                                         jax.tree.leaves(res.params)))
        if not np.array_equal(np.asarray(x), np.asarray(y))
    ]
    assert not mismatches, f"params differ at leaves {mismatches}"


# -------------------------------------------------------- chaos soak

def _load_chaos_soak():
    path = Path(__file__).resolve().parent.parent / "tools" / "chaos_soak.py"
    spec = importlib.util.spec_from_file_location("chaos_soak", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.chaos
def test_chaos_soak_exactly_once_under_faults():
    """The acceptance scenario end to end: live serving under >=10%
    transfer failures + scripted batch-loop crashes; every accepted
    request answered exactly once, shed get 503 + Retry-After, expired
    deadlines 504, nothing lost.  run_soak asserts the invariants
    internally; the summary is re-checked here."""
    soak = _load_chaos_soak()
    summary = soak.run_soak(seed=7, n_requests=24, max_queue=6)
    answered = (summary["answered_200"] + summary["shed_503"])
    assert answered == 24 and summary["lost"] == 0
    assert summary["faults_fired"]["serving.batch_loop"] >= 2
    assert summary["faults_fired"]["feed.device_put"] >= 1
    assert summary["recoveries"] >= 2     # the supervisor actually worked
    assert json.dumps(summary)            # JSON-able for CI artifacts
