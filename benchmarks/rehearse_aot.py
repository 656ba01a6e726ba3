"""Ask the chip's compiler before asking the chip.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_aot.py [--only <cell>]

Compiles, for a DESCRIBED `v5e:2x2` (the TPU compiler is installed here, no
device is attached), every program the cells of BENCHMARK.json run at their
real shapes, read from the cells' own files: the flash forward and backward
kernels at the training batch, the training epoch (with the search for the
largest batch that fits), the paged decode step and the admission prefills,
the 3D step on the 2x2 mesh.  Prints `memory_analysis()` per device and the
counts of custom calls and collectives, one JSON line per program.

Nothing runs: a compile that passes is a compile fact, never a chip run.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
HBM_BYTES = 16e9
EPOCH_BUDGET_BYTES = 14.5e9
COLLECTIVES = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\(")


def report(name: str, compiled, t0: float, **extra) -> dict:
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    rec = {
        "program": name,
        "compile_s_here": round(time.time() - t0, 1),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        # what the program holds at once on one device: donated arguments
        # are the outputs' buffers
        "total_bytes": (mem.argument_size_in_bytes + mem.output_size_in_bytes
                        - mem.alias_size_in_bytes + mem.temp_size_in_bytes),
        "custom_calls": text.count("tpu_custom_call"),
        "collectives": dict(collections.Counter(COLLECTIVES.findall(text))),
        **extra}
    rec["fits_16GB"] = rec["total_bytes"] < HBM_BYTES
    print(json.dumps(rec), flush=True)
    return rec


def shapes_of(fn, sharding, *args):
    """`jax.eval_shape`, with every leaf placed by `sharding`."""
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(fn, *args))


def lm_train(cell, one, replicated) -> list:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lib.lm import build_lm
    from mmlspark_tpu.models.training import make_lm_train_epoch
    from mmlspark_tpu.ops.attention_kernels import fused_attention

    seq = cell.traffic["seq_len"]
    s, model = build_lm(cell.config, seq)
    steps, batch = cell.params["steps_per_epoch"], cell.params["batch"]
    out = []
    q = jax.ShapeDtypeStruct((batch, seq, s["heads"], s["head_dim"]),
                             jnp.bfloat16, sharding=replicated)
    t0 = time.time()
    fwd = jax.jit(lambda q, k, v: fused_attention(q, k, v, True))
    out.append(report("flash_forward", fwd.lower(q, q, q).compile(), t0,
                      shape=list(q.shape), custom_calls_expected=1))
    t0 = time.time()
    bwd = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        fused_attention(q, k, v, True).astype(jnp.float32) ** 2), (0, 1, 2)))
    out.append(report("flash_forward_dkdv_dq", bwd.lower(q, q, q).compile(),
                      t0, shape=list(q.shape), custom_calls_expected=3))

    opt = optax.adam(cell.params["learning_rate"])
    params = shapes_of(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))["params"],
        replicated)
    opt_state = shapes_of(opt.init, replicated, params)
    epoch = make_lm_train_epoch(model, opt, mesh=one)
    best = None
    for b in range(2, 17, 2):
        tokens = jax.ShapeDtypeStruct(
            (steps, b, seq), jnp.int32,
            sharding=NamedSharding(one, P(None, "data")))
        t0 = time.time()
        try:
            compiled = epoch.lower(params, opt_state, tokens).compile()
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the answer
            print(json.dumps({"program": f"lm_train_epoch_b{b}",
                              "refused": str(e)[-400:]}), flush=True)
            break
        rec = report(f"lm_train_epoch_b{b}", compiled, t0, batch=b,
                     custom_calls_expected=3 * s["layers"])
        out.append(rec)
        if rec["total_bytes"] > EPOCH_BUDGET_BYTES:
            break
        best = b
    print(json.dumps({"program": "lm_train_epoch", "largest_batch_that_fits":
                      best, "budget_bytes": EPOCH_BUDGET_BYTES,
                      "workload_file_says": batch}), flush=True)
    return out


def lm_serve(cell, one, replicated) -> list:
    import jax
    import jax.numpy as jnp

    from lib.lm import build_lm
    from mmlspark_tpu.models.generation import _prefill_cache

    s, model = build_lm(cell.config, cell.config["n_positions"])
    slots, page = cell.params["max_slots"], cell.params["page_size"]
    mp = s["positions"] // page
    pool = jax.ShapeDtypeStruct((slots * mp + 1, page, s["heads"],
                                 s["head_dim"]), jnp.bfloat16,
                                sharding=replicated)
    variables = {"params": shapes_of(lambda: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        replicated)}

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=replicated)

    out = []
    step = jax.jit(lambda v, t, c, p, pt: model.apply(
        v, t, c, p, pt, method=model.decode_step))
    cache = tuple((pool, pool) for _ in range(s["layers"]))
    t0 = time.time()
    out.append(report("paged_decode_step", step.lower(
        variables, i32(slots, 1), cache, i32(slots), i32(slots, mp)).compile(),
        t0, slots=slots, custom_calls_expected=s["layers"]))
    prefill = jax.jit(lambda v, toks: _prefill_cache(model, v, toks, None))
    from drivers.lm_serve import _buckets

    p = cell.traffic["prompt_len"]
    shapes = [(1, b) for b in _buckets(p["min"], p["max"])]
    shapes.append((slots, shapes[-1][1]))       # the largest admission
    for rows, bucket in shapes:
        t0 = time.time()
        out.append(report(f"prefill_r{rows}_b{bucket}", prefill.lower(
            variables, i32(rows, bucket)).compile(), t0, rows=rows,
            bucket=bucket))
    return out


def lm_train_3d(cell, topo) -> list:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lib.lm import build_lm
    from mmlspark_tpu.models.training import (lm_params_to_3d,
                                              make_lm_train_step_3d)
    from mmlspark_tpu.parallel.mesh import MeshContext, MeshPlan
    from mmlspark_tpu.parallel.sharding_rules import (lm_3d_rules,
                                                      match_partition_rules)

    seq = cell.traffic["seq_len"]
    s, model = build_lm(cell.config, seq)
    mesh_shape = cell.config["deployment"]["mesh"]
    plan = MeshPlan(devices=list(topo.devices), **mesh_shape)
    a, m, mb = (cell.params[k] for k in ("accum", "micro", "mb"))
    p3 = jax.eval_shape(lambda: lm_params_to_3d(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, seq), jnp.int32))["params"],
        s["layers"], mesh_shape["pipe"]))
    specs = match_partition_rules(lm_3d_rules(), p3)
    p3 = jax.tree.map(lambda x, spec: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(plan.mesh, spec)), p3, specs)
    opt = optax.adam(cell.params["learning_rate"])
    out = []
    with MeshContext(plan.mesh):
        # Adam's moments mirror the parameters' shardings (the driver
        # makes them with an eager opt.init, which keeps them); the rest
        # of the state is replicated
        like = jax.tree.structure(p3)
        o3 = jax.tree.map(
            lambda node: (jax.tree.map(
                lambda x, ref: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=ref.sharding), node, p3)
                if jax.tree.structure(node) == like
                else jax.ShapeDtypeStruct(
                    node.shape, node.dtype,
                    sharding=NamedSharding(plan.mesh, P()))),
            jax.eval_shape(opt.init, p3),
            is_leaf=lambda node: jax.tree.structure(node) == like)
        step = make_lm_train_step_3d(model, opt, plan,
                                     remat=cell.params["remat"])
        tokens = jax.ShapeDtypeStruct(
            (a, m, mb, seq), jnp.int32,
            sharding=NamedSharding(plan.mesh, P(None, None, "data", None)))
        t0 = time.time()
        out.append(report("lm_train_step_3d", step.lower(p3, o3, tokens)
                          .compile(), t0, tokens=[a, m, mb, seq],
                          mesh=mesh_shape, per_device=True))
    return out


def featurize(cell, one, replicated) -> list:
    import jax
    import numpy as np

    from mmlspark_tpu.models.bundle import FlaxBundle
    from mmlspark_tpu.models.tpu_model import ImagePreprocess

    cfg = cell.config
    side = cfg["image_size"]
    bundle = FlaxBundle(cfg["builder"], {"num_classes": cfg["num_classes"]},
                        input_shape=(side, side, 3), seed=0)
    pre = ImagePreprocess(side, side, mean=[103.53, 116.28, 123.675],
                          std=[57.375, 57.12, 58.395])
    variables = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=replicated), bundle.variables)
    fwd = jax.jit(lambda v, x: bundle.apply(v, pre(x, mesh=one))["pool"])
    out = []
    for h, w in cell.traffic["sizes"]:
        x = jax.ShapeDtypeStruct((cell.params["batch_size"], h, w, 3),
                                 np.uint8, sharding=replicated)
        t0 = time.time()
        out.append(report(f"featurizer_forward_{h}x{w}",
                          fwd.lower(variables, x).compile(), t0,
                          batch=cell.params["batch_size"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", help="one cell of BENCHMARK.json")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import run as harness
    from mmlspark_tpu.parallel.mesh import MeshContext, make_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    one = make_mesh(devices=[topo.devices[0]])
    replicated = NamedSharding(one, P())
    programs = {"lm_train": lm_train, "lm_serve": lm_serve,
                "featurize": featurize}
    results = {}
    for entry in manifest["workloads"]:
        if args.only and entry["name"] != args.only:
            continue
        cell = harness.Cell(manifest, entry["name"], rehearse=False)
        kind = cell.workload["driver"]
        print(json.dumps({"cell": cell.name, "driver": kind}), flush=True)
        if kind == "lm_train_3d":
            results[cell.name] = lm_train_3d(cell, topo)
        elif kind in programs:
            with MeshContext(one):
                results[cell.name] = programs[kind](cell, one, replicated)
        else:
            print(json.dumps({"cell": cell.name, "skipped":
                              f"no rehearsal for driver {kind}"}), flush=True)
    os.makedirs(os.path.join(BENCH, "out"), exist_ok=True)
    with open(os.path.join(BENCH, "out", "rehearse_aot.json"), "w") as f:
        json.dump(results, f, indent=1)
    bad = [r["program"] for rs in results.values() for r in rs
           if not r["fits_16GB"] or r.get("custom_calls_expected",
                                          r["custom_calls"])
           != r["custom_calls"]]
    print(json.dumps({"rehearse_aot": "ok" if not bad else "failed",
                      "programs": sum(map(len, results.values())),
                      "bad": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
