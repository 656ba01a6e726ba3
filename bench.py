"""North-star benchmark: ResNet-50 ImageFeaturizer images/sec on one chip.

BASELINE.json metric: "ImageFeaturizer images/sec/chip (ResNet-50)".  The
reference publishes no absolute number (BASELINE.md), so the recorded
baseline is the same path on a host CPU via XLA-CPU
(BENCH_BASELINE.json); vs_baseline is the TPU/CPU throughput ratio.

What is measured (the full ImageFeaturizer.transform call stack, matching
ImageFeaturizer.scala:137-184: decode -> device resize/normalize -> ResNet-50
forward -> feature fetch):
  - value        : end-to-end ImageFeaturizer images/sec (JPEG bytes in,
                   pooled features out)
  - forward_ips  : jitted backbone-only images/sec (upper bound)
  - mfu          : achieved FLOP/s / chip peak bf16 FLOP/s, using XLA's own
                   cost analysis for the FLOP count (north star: >90% util)

One process holds the chip and runs every phase.  There is no CPU mode and
no replay: without a TPU `python bench.py` exits non-zero and prints no
number; a device kind the peaks table does not know raises; a phase that
fails is named on stderr and the exit code is non-zero.  (`--lm3d` is a
separate entry: the layout sweep of the 3D trainer on the 8-device virtual
CPU mesh, whose record is a CPU record and never joins a chip record.)

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""
import io
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

HERE = os.path.dirname(os.path.abspath(__file__))
BASELINE_FILE = os.path.join(HERE, "BENCH_BASELINE.json")
LASTGOOD_FILE = os.path.join(HERE, "BENCH_LASTGOOD.json")

# Stamped into every record as "schema"; tools/perf_gate.py cross-checks it
# against BENCH_LASTGOOD.json and flags a STALE BASELINE on mismatch.  Bump
# whenever the record's key set or the methodology behind a gated metric
# changes, so a pre-change baseline can't silently gate the new numbers.
BENCH_SCHEMA = 2

BATCH = 128
# the e2e feed batches large: bigger device_put chunks amortize the fixed
# per-transfer cost
E2E_BATCH = 256
ITERS = 10
IMG = 224
N_E2E = 512

# bf16 peak FLOP/s per chip by device kind substring (public TPU specs)
PEAK_FLOPS = [
    ("v6", 918e12), ("trillium", 918e12),
    ("v5p", 459e12), ("v5 lite", 197e12), ("v5e", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 46e12),
]


def _best_of(run, iters: int, reps: int = 3) -> float:
    """Best-of-`reps` wall seconds for `iters` dispatches of `run()` (which
    must return a value to block on).  tools/mfu_sweep.py's `_bench_ms`
    delegates here, so every recorded number shares this methodology."""
    import jax

    jax.block_until_ready(run())  # warm
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            y = run()
        jax.block_until_ready(y)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def _chip_peak_flops() -> float:
    """bf16 peak of the attached chip; a device kind that is not in
    PEAK_FLOPS is an error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    for sub, peak in PEAK_FLOPS:
        if sub in kind.lower():
            return peak
    raise ValueError(
        f"unknown device_kind {kind!r}: add its bf16 peak to "
        "bench.PEAK_FLOPS before reporting a utilization")


JPEG_SIZES = ((256, 256), (224, 224), (320, 240))  # (h, w), round robin


def _synthetic_jpeg_table(n: int, sizes=JPEG_SIZES, seed: int = 0):
    """A Table of n JPEG-encoded noise images (mixed sizes, like a real
    directory scan would produce), made from `seed`."""
    import numpy as np
    from PIL import Image

    from mmlspark_tpu import Table

    rng = np.random.default_rng(seed)
    blobs = []
    for i in range(n):
        h, w = sizes[i % len(sizes)]
        arr = rng.integers(0, 256, size=(h, w, 3), dtype=np.uint8)
        buf = io.BytesIO()
        Image.fromarray(arr).save(buf, format="JPEG", quality=85)
        blobs.append(buf.getvalue())
    return Table({"image": blobs})


def _measure_train(batch: int = 256, steps: int = 40) -> dict:
    """CIFAR10-shape data-parallel training throughput (the second headline
    config in BASELINE.json: 'CIFAR10 train samples/sec'; reference
    notebooks/DeepLearning - CIFAR10).  A full epoch of fwd + bwd + SGD
    steps on ResNet-18 at 32x32 runs as ONE scanned dispatch
    (make_train_epoch), so per-call latency doesn't gate the measurement —
    the same shape a real TPU training loop uses."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mmlspark_tpu.models.resnet import resnet18
    from mmlspark_tpu.models.training import init_train_state, make_train_epoch
    from mmlspark_tpu.parallel.mesh import MeshContext, make_mesh

    mesh = make_mesh(data=len(jax.devices()))
    model = resnet18(num_classes=10, dtype=jnp.bfloat16)
    opt = optax.sgd(0.1, momentum=0.9)
    with MeshContext(mesh):
        state = init_train_state(model, opt, (32, 32, 3))
        epoch = make_train_epoch(model, opt, num_classes=10, mesh=mesh,
                                 donate=True)
        sh = NamedSharding(mesh, P(None, "data"))
        # synthetic epoch data generated ON DEVICE: the metric is training
        # throughput, and shipping ~0.5GB of noise from the host would
        # swamp the measurement with data-loading cost
        gen = jax.jit(
            lambda k: (jax.random.normal(
                k, (steps, batch, 32, 32, 3), jnp.float32),
                jax.random.randint(k, (steps, batch), 0, 10, jnp.int32)),
            out_shardings=(sh, sh))
        images, labels = gen(jax.random.PRNGKey(0))
        jax.block_until_ready(images)
        state, ms = epoch(state, images, labels)       # compile
        jax.block_until_ready(ms["loss"])
        t0 = time.perf_counter()
        state, ms = epoch(state, images, labels)
        jax.block_until_ready(ms["loss"])
        dt = time.perf_counter() - t0
    return {"cifar10_train_samples_per_sec": round(steps * batch / dt, 1)}


def _measure_guard(steps: int = 96, batch: int = 32,
                   reps: int = 5) -> dict:
    """Host-loop cost of the training-guard plumbing (PR 10).  The
    reliability ladder's contract is that the guard-DISABLED path —
    fit_epochs_resumable's default, where every fault point is disarmed
    and every guard branch short-circuits on `guard is None` — adds
    <1% per-step overhead versus the bare pre-guard loop body.  Measured
    here as the median per-step wall of fit_epochs_resumable(guard=None)
    against a reference loop with the identical feed/span/step body and
    no guard/checkpoint plumbing at all; perf_gate bands
    `guard_overhead_frac`.  The guard-ENABLED fraction rides along as an
    informational field (it buys the whole anomaly ladder; it is not
    gated)."""
    import statistics
    import tempfile

    import jax
    import optax
    import flax.linen as nn
    import numpy as np

    from mmlspark_tpu.core import telemetry as core_telemetry
    from mmlspark_tpu.io.feed import DeviceFeed
    from mmlspark_tpu.models.guard import TrainingGuard
    from mmlspark_tpu.models.training import (fit_epochs_resumable,
                                              init_train_state,
                                              make_train_step)
    from mmlspark_tpu.parallel.mesh import batch_sharding, default_mesh

    class M(nn.Module):
        # sized so one step costs ~1-3 ms: a 4x4 micro-model would make
        # the denominator so small that microseconds of host plumbing
        # read as whole percents, gating noise instead of overhead
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(256)(x))
            return nn.Dense(4)(x), {}

    mesh = default_mesh()
    model, opt = M(), optax.sgd(0.1)
    n = steps * batch
    gen = np.random.default_rng(0)
    imgs = gen.normal(size=(n, 16, 16, 3)).astype(np.float32)
    lbls = gen.integers(0, 4, size=n).astype(np.int32)
    step = make_train_step(model, opt, 4, mesh=mesh, donate=False)
    state0 = init_train_state(model, opt, (16, 16, 3), seed=0)
    img_sh = batch_sharding(mesh, 4)
    lbl_sh = batch_sharding(mesh, 1)
    # compile outside every timed window
    jax.block_until_ready(step(state0, imgs[:batch], lbls[:batch])[1]["loss"])

    def median_step_s(times):
        # consecutive log/loop timestamps: excludes manager setup,
        # resume probing, and the final checkpoint write
        deltas = [b - a for a, b in zip(times, times[1:])]
        return statistics.median(deltas[2:])  # drop warm-in steps

    def run_reference():
        """The pre-guard loop body, verbatim: feed + span + step +
        host-float metric pulls + latency instrumentation + the same
        log_fn call shape (int(state.step) pulls a device scalar — both
        sides must pay it)."""
        order = np.random.default_rng([7, 0]).permutation(n)
        feed = DeviceFeed(mesh=mesh)
        state, times = state0, []
        for g in range(steps):
            idx = order[g * batch:(g + 1) * batch]
            dbi, dbl = feed.put_group([imgs[idx], lbls[idx]],
                                      shardings=(img_sh, lbl_sh))
            t0 = time.perf_counter()
            with core_telemetry.span("training.step"):
                state, m = step(state, dbi, dbl)
                metrics = {k: float(v) for k, v in m.items()}
            dt = time.perf_counter() - t0
            core_telemetry.histogram(
                "models.training.step_latency").observe(dt)
            core_telemetry.gauge("models.training.examples_per_sec").set(
                batch / dt if dt > 0 else 0.0)
            _ = (int(state.step), metrics)
            times.append(time.perf_counter())
        return median_step_s(times)

    def run_resumable(guard):
        times = []
        with tempfile.TemporaryDirectory() as ck:
            fit_epochs_resumable(
                step, state0, imgs, lbls, batch_size=batch,
                checkpoint_dir=ck, epochs=1, checkpoint_every=10**9,
                mesh=mesh, seed=7, guard=guard,
                log_fn=lambda s, m: times.append(time.perf_counter()))
        return median_step_s(times)

    # interleaved best-of-N: min-of-medians cancels machine-load drift
    # that a single pair of runs (≈±4% on a busy host) would bake into
    # the fraction — the band on this metric is one absolute point
    refs, dis = [], []
    for _ in range(reps):
        refs.append(run_reference())
        dis.append(run_resumable(None))
    enabled = run_resumable(TrainingGuard(hang_timeout_s=3600.0))
    ref, disabled = min(refs), min(dis)
    # clamp at zero: the resumable loop is a superset of the reference,
    # so a negative fraction is measurement noise — and a negative
    # LASTGOOD base would tighten perf_gate's absolute band for free
    return {
        "guard_overhead_frac": max(0.0, round((disabled - ref) / ref, 4)),
        "guard_enabled_overhead_frac": round((enabled - ref) / ref, 4),
    }


def _measure_timeseries_overhead(steps: int = 96, batch: int = 32,
                                 reps: int = 5) -> dict:
    """Per-step cost of the goodput plane (PR 20): the
    `LEDGER.record_step` + `STORE.tick` pair fit_epochs_resumable now
    executes every step.  The contract is <1% of step wall — perf_gate
    bands `timeseries_overhead_frac` absolutely at one point, same shape
    as the guard/sanitizer disabled-path contracts.  Measured as an
    interleaved min-of-medians of the identical feed+step body with and
    without the two calls (methodology of _measure_guard)."""
    import statistics

    import jax
    import optax
    import flax.linen as nn
    import numpy as np

    from mmlspark_tpu.core import telemetry as core_telemetry
    from mmlspark_tpu.core.telemetry.goodput import GoodputLedger
    from mmlspark_tpu.core.telemetry.timeseries import TimeSeriesStore
    from mmlspark_tpu.io.feed import DeviceFeed
    from mmlspark_tpu.models.training import (init_train_state,
                                              make_train_step)
    from mmlspark_tpu.parallel.mesh import batch_sharding, default_mesh

    class M(nn.Module):
        # same sizing rationale as _measure_guard: the denominator must
        # be a real 1-3 ms step, not a microsecond no-op
        @nn.compact
        def __call__(self, x, train=False):
            x = x.reshape((x.shape[0], -1))
            x = nn.relu(nn.Dense(256)(x))
            return nn.Dense(4)(x), {}

    mesh = default_mesh()
    model, opt = M(), optax.sgd(0.1)
    n = steps * batch
    gen = np.random.default_rng(0)
    imgs = gen.normal(size=(n, 16, 16, 3)).astype(np.float32)
    lbls = gen.integers(0, 4, size=n).astype(np.int32)
    step = make_train_step(model, opt, 4, mesh=mesh, donate=False)
    state0 = init_train_state(model, opt, (16, 16, 3), seed=0)
    img_sh = batch_sharding(mesh, 4)
    lbl_sh = batch_sharding(mesh, 1)
    jax.block_until_ready(step(state0, imgs[:batch], lbls[:batch])[1]["loss"])

    led = GoodputLedger(host_id="bench")
    store = TimeSeriesStore()

    def median_step_s(times):
        deltas = [b - a for a, b in zip(times, times[1:])]
        return statistics.median(deltas[2:])  # drop warm-in steps

    def run(instrumented):
        order = np.random.default_rng([7, 0]).permutation(n)
        feed = DeviceFeed(mesh=mesh)
        state, times = state0, []
        led.reset("bench")
        store.reset()
        for g in range(steps):
            idx = order[g * batch:(g + 1) * batch]
            dbi, dbl = feed.put_group([imgs[idx], lbls[idx]],
                                      shardings=(img_sh, lbl_sh))
            t0 = time.perf_counter()
            state, m = step(state, dbi, dbl)
            metrics = {k: float(v) for k, v in m.items()}
            dt = time.perf_counter() - t0
            core_telemetry.histogram(
                "models.training.step_latency").observe(dt)
            if instrumented:
                led.record_step(g, compute_s=dt, h2d=0.0)
                store.tick()
            _ = (int(state.step), metrics)
            times.append(time.perf_counter())
        return median_step_s(times)

    refs, ins = [], []
    for _ in range(reps):
        refs.append(run(False))
        ins.append(run(True))
    ref, inst = min(refs), min(ins)
    return {
        "timeseries_overhead_frac": max(0.0, round((inst - ref) / ref, 4)),
    }


def _measure_sanitizer(n_items: int = 400, reps: int = 5) -> dict:
    """Disabled-path cost of the runtime concurrency sanitizer hooks
    (tools/graftsan).  The flow runtime carries `_SAN is not None`
    branches at every credit acquire/release and EOF enqueue, plus the
    `make_lock` factory indirection at lock construction; the contract
    is that with graftsan NOT installed those cost <1% of the flow
    runtime's per-item wall.  Measured as min-of-medians per-item wall
    of a 2-stage FlowGraph against a reference run with the pre-hook
    `_Credits.acquire/release` and `FlowGraph._enqueue` bodies swapped
    back in verbatim; perf_gate bands `sanitizer_overhead_frac`.  The
    sanitizer-ENABLED fraction rides along informationally (it buys the
    lockset/credit audits; it is not gated)."""
    import queue as queue_mod
    import statistics

    from mmlspark_tpu.core import flow as flow_mod
    from mmlspark_tpu.core import telemetry as core_telemetry
    from mmlspark_tpu.core.flow import _POLL_S, FlowGraph, Stage

    def run_once() -> float:
        g = FlowGraph([Stage("san_bench_a", fn=lambda x: x + 1, workers=2),
                       Stage("san_bench_b", fn=lambda x: x * 2, workers=2)],
                      queue_size=8, label="sanitizer-bench")
        t0 = time.perf_counter()
        n = sum(1 for _ in g.run(range(n_items)))
        dt = time.perf_counter() - t0
        assert n == n_items
        return dt / n_items

    # the pre-hook bodies, verbatim (minus the _SAN lines) — swapped in
    # for the reference runs so both sides pay identical queue/credit/
    # telemetry work and differ ONLY by the disabled-hook branches
    def _ref_acquire(self, cancelled) -> bool:
        while not cancelled.is_set():
            if self._sem.acquire(timeout=_POLL_S):
                return True
        return False

    def _ref_release(self) -> None:
        self._sem.release()

    def _ref_enqueue(self, idx, item):
        q = self._queues[idx]
        while not self._cancelled.is_set():
            try:
                q.put(item, timeout=_POLL_S)
                break
            except queue_mod.Full:
                continue
        name = self._qnames[idx]
        depth = q.qsize()
        self._note_depth(name, depth)
        core_telemetry.gauge(f"flow.queue.depth.{name}").set(depth)
        if self._on_depth is not None:
            self._on_depth(name, depth)

    hooked = (flow_mod._Credits.acquire, flow_mod._Credits.release,
              flow_mod.FlowGraph._enqueue)

    def run_median(patched: bool) -> float:
        if patched:
            flow_mod._Credits.acquire = _ref_acquire
            flow_mod._Credits.release = _ref_release
            flow_mod.FlowGraph._enqueue = _ref_enqueue
        try:
            return statistics.median(run_once() for _ in range(3))
        finally:
            (flow_mod._Credits.acquire, flow_mod._Credits.release,
             flow_mod.FlowGraph._enqueue) = hooked

    # interleaved best-of-N: min-of-medians cancels machine-load drift
    # (same methodology as guard_overhead_frac — the band is absolute)
    refs, live = [], []
    for _ in range(reps):
        refs.append(run_median(patched=True))
        live.append(run_median(patched=False))
    import tools.graftsan as graftsan

    try:
        graftsan.install()
        enabled = run_median(patched=False)
    finally:
        graftsan.uninstall()
    ref, disabled = min(refs), min(live)
    # clamp at zero: the hooked path is a superset of the reference, so
    # a negative fraction is noise — and a negative LASTGOOD base would
    # tighten perf_gate's absolute band for free
    return {
        "sanitizer_overhead_frac": max(
            0.0, round((disabled - ref) / ref, 4)),
        "sanitizer_enabled_overhead_frac": round(
            (enabled - ref) / ref, 4),
    }


def _measure_fleet_scrape(n_replicas: int = 8, reps: int = 5,
                          warm_requests: int = 16) -> dict:
    """Wall cost of one federated telemetry pull over an 8-replica pool
    (PR 15 fleet plane): `FleetTelemetry.pull_once()` GETs every
    replica's /metrics.json, merges counters/gauges/histograms exactly,
    and runs the SLO engine — all WITHOUT the gateway routing lock, so
    the scrape cost may grow with fleet size but must never stall
    forwarding.  perf_gate bands `fleet_scrape_ms` (best-of-reps)."""
    import numpy as np

    from mmlspark_tpu.core.pipeline import LambdaTransformer
    from mmlspark_tpu.io.http.clients import send_request
    from mmlspark_tpu.io.http.schema import to_http_request
    from mmlspark_tpu.serving import FleetGateway, ServingServer

    def make_replica():
        def fn(table):
            v = np.asarray(table["v"], np.int64)
            return table.with_column("y", v * 3)

        return ServingServer(LambdaTransformer(fn), reply_col="y",
                             name="scrape-bench", input_schema=["v"],
                             max_batch=8, batch_timeout_ms=5.0)

    replicas = [make_replica() for _ in range(n_replicas)]
    gw = FleetGateway(name="scrape-bench", probe_interval_s=5.0)
    try:
        for r in replicas:
            r.start()
            gw.add_server(r, version="v1")
        gw.start()
        # populate every registry view so the merge does real work
        for i in range(warm_requests):
            send_request(to_http_request(gw.url, {"v": i}), timeout=10.0)
        best = None
        for _ in range(reps):
            t0 = time.perf_counter()
            merged = gw.telemetry_plane.pull_once()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        assert merged["meta"]["replica_count"] == n_replicas + 1  # +gateway
        return {"fleet_scrape_ms": round(best * 1e3, 3),
                "fleet_scrape_replicas": n_replicas}
    finally:
        gw.stop()
        for r in replicas:
            try:
                r.stop(drain=False)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass


def _measure_transformer(batch: int = 16, seq: int = 1024,
                         steps: int = 8) -> dict:
    """TransformerLM train-step throughput + MFU — the matmul-dominated
    workload where high MFU is actually available on the MXU (the CNN
    forward's roofline caps near 0.47; see tools/roofline.py and
    docs/performance.md).  GPT-small-ish config, bf16, fwd+bwd+adam as
    ONE jitted step; FLOPs from XLA's own cost analysis."""

    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.models.training import make_lm_train_epoch
    from mmlspark_tpu.models.transformer import transformer_lm

    model = transformer_lm(vocab_size=8192, embed_dim=768, num_layers=12,
                           num_heads=12, max_len=seq, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    # the whole epoch of minibatches scans as ONE dispatch — per-step host
    # round trips must not gate the number
    tokens = jax.random.randint(rng, (steps, batch, seq), 0, 8192, jnp.int32)
    params = jax.jit(lambda r, t: model.init(r, t)["params"])(
        rng, tokens[0])
    opt = optax.adam(3e-4)
    opt_state = jax.jit(opt.init)(params)
    epoch = make_lm_train_epoch(model, opt, donate=False)
    # per-step FLOPs from a ONE-step epoch: XLA's cost analysis counts a
    # scan body once regardless of trip count, so the full-epoch program
    # would undercount by `steps`x.  The COMPILED analysis: on the TPU
    # backend `Lowered.cost_analysis()` is None (PR 21, on the chip).
    flops_step = float(epoch.lower(params, opt_state, tokens[:1]).compile()
                       .cost_analysis()["flops"])
    compiled = epoch.lower(params, opt_state, tokens).compile()
    best = _best_of(lambda: compiled(params, opt_state, tokens)[2], iters=1)
    return {
        "lm_tokens_per_sec": round(steps * batch * seq / best, 0),
        "lm_train_mfu": round(
            steps * flops_step / best / _chip_peak_flops(), 4),
    }


LM3D_LAYOUTS = (((8, 1, 1), (2, 1)), ((2, 4, 1), (2, 2)),
                ((2, 2, 2), (2, 2)))  # ((D, T, P), (accum, microbatches))


def _lm3d_child():
    """Runs in its own subprocess with JAX_PLATFORMS=cpu and an 8-device
    virtual mesh (the env is set by the PARENT before this process
    imports jax — host_platform_device_count binds at import).  Sweeps
    the (D, T, P) layouts of the 3D-mesh GSPMD trainer and prints one
    JSON line; the remat saving is read off XLA's own memory analysis of
    the same program compiled both ways."""
    import jax
    import jax.numpy as jnp
    import optax

    from mmlspark_tpu.models.training import (lm_params_to_3d,
                                              make_lm_train_step_3d,
                                              shard_params)
    from mmlspark_tpu.models.transformer import transformer_lm
    from mmlspark_tpu.parallel.mesh import MeshPlan
    from mmlspark_tpu.parallel.sharding_rules import lm_3d_rules

    V, E, L, H, S = 2048, 256, 4, 8, 256
    model = transformer_lm(vocab_size=V, embed_dim=E, num_layers=L,
                           num_heads=H, max_len=S, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    toks = jax.random.randint(jax.random.PRNGKey(1), (16, S), 0, V,
                              jnp.int32)
    params = jax.jit(lambda r, t: model.init(r, t)["params"])(rng, toks[:2])
    opt = optax.adam(3e-4)

    out = {"lm3d_layouts": {}, "grad_accum_steps": None}
    best_ms, best_exec = None, None
    for (d, t, p), (a, m) in LM3D_LAYOUTS:
        plan = MeshPlan(data=d, model=t, pipe=p)
        p3 = shard_params(lm_params_to_3d(params, L, p), plan.mesh,
                          lm_3d_rules())
        os3 = opt.init(p3)
        step = make_lm_train_step_3d(model, opt, plan, remat=True,
                                     donate=False)
        tb = toks.reshape(a, m, 16 // (a * m), S)
        compiled = step.lower(p3, os3, tb).compile()
        ms = _best_of(lambda: compiled(p3, os3, tb)[2]["loss"],
                      iters=1) * 1e3
        out["lm3d_layouts"][f"{d}x{t}x{p}"] = round(ms, 2)
        out["grad_accum_steps"] = a
        if best_ms is None or ms < best_ms:
            best_ms, best_exec = ms, (compiled, p3, os3, tb)
    out["lm3d_step_ms"] = round(best_ms, 2)

    # goodput-plane rider (PR 20): a few explicitly timed steps of the
    # winning layout through a fresh ledger, so the sweep record carries
    # goodput_frac and the lost-time table alongside step_ms
    from mmlspark_tpu.core.telemetry.goodput import GoodputLedger
    led = GoodputLedger(host_id="lm3d")
    compiled_b, pb, ob, tbb = best_exec
    for i in range(4):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled_b(pb, ob, tbb)[2]["loss"])
        led.record_step(i, compute_s=time.perf_counter() - t0)
    summ = led.summary()
    out["goodput_frac"] = summ["goodput_frac"]
    out["lost_time_breakdown"] = summ["lost"]

    # remat saving at the full-3D layout: identical program, one compile
    # with block remat and one without — the delta is the activation
    # memory the dots-saveable policy trades for recompute
    plan = MeshPlan(data=2, model=2, pipe=2)
    p3 = shard_params(lm_params_to_3d(params, L, 2), plan.mesh,
                      lm_3d_rules())
    os3 = opt.init(p3)
    tb = toks.reshape(2, 2, 4, S)
    mems = {}
    for remat in (False, True):
        step = make_lm_train_step_3d(model, opt, plan, remat=remat,
                                     donate=False)
        mems[remat] = int(step.lower(p3, os3, tb).compile()
                          .memory_analysis().temp_size_in_bytes)
    out["remat_hbm_saved_bytes"] = mems[False] - mems[True]
    out["platform"] = jax.devices()[0].platform
    print(json.dumps(out))


def _measure_lm_3d(timeout: int = 900) -> dict:
    """Parent-side wrapper of the standalone `--lm3d` entry: the sweep
    ALWAYS runs on the 8-device virtual CPU mesh (layout comparison
    needs 8 homogeneous devices) — a fresh subprocess gets the forced
    env because device count binds at jax import.  The parent never
    imports jax, the child is CPU-pinned and needs no chip, and its
    record is a CPU record (`"platform": "cpu"`)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"
                          ).strip())
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--lm3d-child"],
        capture_output=True, text=True, timeout=timeout, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"--lm3d child exited {proc.returncode}: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _measure_vit(batch: int = 128, iters: int = 10) -> dict:
    """ViT-B/16 bf16 inference MFU — the matmul-dominated vision backbone.
    ResNet-50's roofline caps near 0.47 MFU on a v5e (docs/performance.md);
    ViT is where a vision workload actually reaches the >=0.5 MFU goal, so
    the record carries both numbers."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.bundle import FlaxBundle

    bundle = FlaxBundle("vit_base", {"num_classes": 1000},
                        input_shape=(IMG, IMG, 3))
    dev_vars = jax.device_put(
        jax.tree.map(lambda x: jnp.asarray(x, jnp.bfloat16), bundle.variables))
    jitted = jax.jit(lambda v, x: bundle.apply(v, x)["pool"])
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, IMG, IMG, 3)), jnp.bfloat16)
    compiled = jitted.lower(dev_vars, x).compile()
    flops = float(compiled.cost_analysis()["flops"])
    best = _best_of(lambda: compiled(dev_vars, x), iters)
    return {
        "vit_ips": round(iters * batch / best, 1),
        "vit_mfu": round(iters * flops / best / _chip_peak_flops(), 4),
    }


def _measure_bottlenecks(table) -> dict:
    """Decompose the e2e ImageFeaturizer number into its three serial-ish
    stages so the forward-vs-e2e gap is a measurement, not an assertion
    (round-3 verdict weak #3): e2e ~= min(decode, transfer, forward).

      decode_ips : native libjpeg probe+decode into preallocated buffers —
                   the exact host work `_transform_bytes_streaming` does on
                   the prefetch thread (image_featurizer.py:175-198)
      h2d_gbps   : achieved `jax.device_put` bandwidth for one uint8 feed
                   chunk of the e2e shape; h2d_ips is that bandwidth in
                   images/sec at the same per-image byte cost
    """
    import jax
    import numpy as np

    from mmlspark_tpu import native

    out: dict = {}
    blobs = [bytes(v) for v in table["image"]]
    if native.jpeg_available():
        shapes = [native.jpeg_probe(b) for b in blobs]
        bufs = [np.zeros(s, np.uint8) for s in shapes]
        best = None
        for _ in range(3):
            t0 = time.perf_counter()
            for b, buf in zip(blobs, bufs):
                native.decode_jpeg_bgr_into(b, buf)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        out["decode_ips"] = round(len(blobs) / best, 1)

    chunk = np.zeros((E2E_BATCH, IMG, IMG, 3), np.uint8)
    best = None
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(chunk))
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    out["h2d_gbps"] = round(chunk.nbytes / best / 1e9, 4)
    out["h2d_ips"] = round(E2E_BATCH / best, 1)
    return out


def _measure(e2e_n: int, batch: int, iters: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
    from mmlspark_tpu.models.bundle import FlaxBundle

    bundle = FlaxBundle("resnet50", {"num_classes": 1000}, input_shape=(IMG, IMG, 3))
    bundle.variables = jax.tree.map(
        lambda x: np.asarray(x, np.float32), bundle.variables)

    # ---- forward-only (upper bound) + XLA-counted FLOPs ----
    dev_vars = jax.device_put(
        jax.tree.map(lambda x: jnp.asarray(x, jnp.bfloat16), bundle.variables))

    def forward(v, x):
        return bundle.apply(v, x)["pool"]

    jitted = jax.jit(forward)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, IMG, IMG, 3)), jnp.bfloat16)
    compiled = jitted.lower(dev_vars, x).compile()
    flops_per_batch = float(compiled.cost_analysis()["flops"])
    fwd_dt = _best_of(lambda: compiled(dev_vars, x), iters)
    forward_ips = iters * batch / fwd_dt
    mfu = (iters * flops_per_batch / fwd_dt) / _chip_peak_flops()

    # ---- end-to-end ImageFeaturizer.transform (the north-star path) ----
    table = _synthetic_jpeg_table(e2e_n)
    feat = ImageFeaturizer(bundle=bundle, input_col="image",
                           output_col="features", batch_size=E2E_BATCH)
    feat.transform(table)  # warm: compile one program per shape group
    from mmlspark_tpu.core import telemetry as core_telemetry
    from mmlspark_tpu.io.feed import FEED_TELEMETRY, FeedTelemetry
    from mmlspark_tpu.io.pipeline import PIPELINE_TELEMETRY

    # warmup compiled every shape group above; from here to the end of
    # the timed reps any XLA compile is a steady-state recompile — the
    # sentry flags it and the count lands in the record (perf-gated at
    # zero tolerance)
    sentry = core_telemetry.track_compiles()
    sentry.end_warmup()
    hot_before = sum(
        core_telemetry.counters("xla.compile.hot_path").values())
    feed_since = FEED_TELEMETRY.snapshot()
    pipe_since = PIPELINE_TELEMETRY.snapshot()
    reps = 3
    e2e_dt = None
    for _ in range(reps):  # best of 3
        t0 = time.perf_counter()
        out_table = feat.transform(table)
        dt = time.perf_counter() - t0
        e2e_dt = dt if e2e_dt is None else min(e2e_dt, dt)
    assert out_table["features"].shape[0] == e2e_n
    e2e_ips = e2e_n / e2e_dt
    steady_recompiles = (sum(
        core_telemetry.counters("xla.compile.hot_path").values())
        - hot_before)
    # back to warmup mode: the train/vit/lm measurements that follow
    # legitimately compile their own programs
    sentry.reset()
    # HBM pressure + live buffers at peak working set (CPU CI reports
    # only the buffer count; memory_stats-less backends no-op)
    device_mem = core_telemetry.sample_device_memory()
    # the DeviceFeed engine's own counters over the timed transforms:
    # achieved wire bandwidth, the fraction of feed wall time hidden
    # under device compute, and the host-side stall budget — these are
    # what distinguish "the link is slow" from "the feed is serializing"
    feed_delta = FEED_TELEMETRY.delta(feed_since)
    feed = FeedTelemetry.summarize(feed_delta)
    # per-stage breakdown off the input pipeline's stage counters + the
    # feed's transfer/compute counters, averaged per transform: where
    # each image's wall time actually went.  busy_s sums over workers,
    # so a stage's ms can exceed e2e wall when its workers overlap —
    # exactly the signal that the stage is parallelized away.
    pipe_delta = PIPELINE_TELEMETRY.delta(pipe_since)

    def _stage_ms(name):
        rec = pipe_delta.get(name)
        if not rec or not rec.get("items"):
            return None
        return round(rec["busy_s"] / reps * 1e3, 1)

    stage_ms = {
        "decode_ms": _stage_ms("decode"),
        "host_assemble_ms": _stage_ms("assemble"),
        "h2d_ms": round(feed_delta.get("transfer_s", 0.0) / reps * 1e3, 1),
        "forward_ms": round((feed_delta.get("compute_s", 0.0)
                             + feed_delta.get("stall_drain_s", 0.0))
                            / reps * 1e3, 1),
    }
    # the registry view of the same run: per-transfer latency tail off the
    # io.feed.transfer.latency histogram (summarize's counters are totals
    # only — the p95 is what catches a bimodal link)
    obs = core_telemetry.export_snapshot(include_spans=False)
    feed_hist = obs["histograms"].get("io.feed.transfer.latency")
    feed_p95_ms = (round(feed_hist["p95"] * 1e3, 3)
                   if feed_hist and feed_hist["p95"] is not None else None)

    out = {
        "value": round(e2e_ips, 1),
        "forward_ips": round(forward_ips, 1),
        # the h2d-wall headline (ISSUE 14): how much of the jitted
        # forward's throughput the full pipeline delivers — 1.0 means the
        # feed costs nothing
        "e2e_over_forward_frac": (round(e2e_ips / forward_ips, 4)
                                  if forward_ips > 0 else None),
        # which transfer path the timed transforms took
        # (sharded | coalesced | fallback)
        "h2d_path": feed["h2d_path"],
        "mfu": round(mfu, 4),
        "overlap_frac": feed["overlap_frac"],
        "stall_s": feed["stall_s"],
        "feed_gbps": feed["h2d_gbps"],
        "feed_transfer_calls": feed["transfer_calls"],
        "feed_transfer_p95_ms": feed_p95_ms,
        "steady_recompiles": steady_recompiles,
        **{k: v for k, v in stage_ms.items() if v is not None},
        **device_mem,
    }
    # e2e_bound: the stage the pipeline actually spent the most host-
    # visible time in during the measured transforms (the old coarse
    # standalone probes stay in the record as decode_ips/h2d_ips for
    # cross-checking, but no longer drive the attribution)
    bound = {k[:-3].rstrip("_"): v for k, v in stage_ms.items()
             if v is not None and v > 0}
    if bound:
        out["e2e_bound"] = max(bound, key=bound.get)
    out.update(_measure_bottlenecks(table))
    return out


def _obs_out_path():
    """--obs-out PATH from argv (bench predates argparse; flags are
    membership tests)."""
    argv = sys.argv
    if "--obs-out" in argv:
        i = argv.index("--obs-out")
        if i + 1 < len(argv):
            return argv[i + 1]
    return None


def _write_obs_out(path, record, obs):
    """Snapshot file for tools/perf_gate.py: the bench record plus the
    run's registry snapshot."""
    if not path:
        return
    with open(path, "w") as f:
        json.dump({"record": record, "obs": obs}, f)


# (name, measure) in run order; each returns a dict merged into the record
PHASES = (
    ("featurizer", lambda: _measure(N_E2E, BATCH, ITERS)),
    ("cifar_train", _measure_train),
    ("vit", _measure_vit),
    ("lm_train", _measure_transformer),
    ("guard", _measure_guard),
    ("sanitizer", _measure_sanitizer),
    ("timeseries", _measure_timeseries_overhead),
    ("fleet_scrape", _measure_fleet_scrape),
)


def _require_tpu():
    """The devices this process measures on, or SystemExit: a measurement
    path that finds no chip fails — it prints no number."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {devices[0].platform!r}; "
                 "a CPU run yields counts and correctness (the tests), "
                 "never a device number")
    return devices


def _run_phases(phases=PHASES) -> dict:
    """Every phase, in this process, in order.  The first that raises is
    named on stderr and re-raised: no record is printed and the exit code
    is non-zero."""
    res: dict = {}
    for name, measure in phases:
        try:
            res.update(measure())
        except Exception:
            sys.stderr.write(f"bench: phase {name!r} failed\n")
            raise
    return res


def main():
    obs_path = _obs_out_path()
    if "--lm3d-child" in sys.argv:
        _lm3d_child()
        return
    if "--lm3d" in sys.argv:
        # standalone sweep entry (CI / local): defined on the virtual CPU
        # mesh, needs no chip; this parent never imports jax
        print(json.dumps(_measure_lm_3d()))
        return

    from mmlspark_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = _require_tpu()
    _chip_peak_flops()  # an unknown device kind raises before any phase
    baseline = None
    if os.path.exists(BASELINE_FILE):
        with open(BASELINE_FILE) as f:
            baseline = json.load(f).get("cpu_images_per_sec")
    res = _run_phases()

    # the registry's own view of the run rides along so --obs-out saves
    # a self-describing snapshot (meta: backend/devices/pid/timestamp)
    from mmlspark_tpu.core import telemetry as core_telemetry

    now = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    obs = core_telemetry.export_snapshot(include_spans=False, timestamp=now)
    value = res.pop("value")
    record = {
        "metric": "resnet50_imagefeaturizer_images_per_sec_per_chip",
        "value": value,
        "unit": "images/sec",
        "vs_baseline": round(value / baseline, 2) if baseline else 1.0,
        **{k: v for k, v in res.items() if v is not None},
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "measured_at": now,
        "schema": BENCH_SCHEMA,
    }
    with open(LASTGOOD_FILE, "w") as f:
        json.dump(record, f)
    _write_obs_out(obs_path, record, obs)
    print(json.dumps(record))


if __name__ == "__main__":
    main()
