"""Ask the chip's compiler before asking the chip: every Pallas kernel of
chip_smoke.py's phases, at the smoke's real shapes, compiled for a
DESCRIBED v5e (the TPU compiler is installed; no device is attached), plus
the whole lm_train program and the serving decode step.  A kernel that had
passed every interpret-mode test was refused this way (the RLE decoder's
(1, w) blocks), so these guard every later PR at no chip time.

Nothing runs here — a compile that passes is not a chip run.  The shapes
come from chip_smoke.SIZES["full"], so the smoke and this file cannot
drift apart.  Steering is the program's own: the kernels ask the devices
their computation targets (ops.pallas_kernels.on_tpu), and the tests
declare those with MeshContext(<described device>).  Named `aot_` so the
file runs first: tier-1 is cut by its clock and a file named late guards
nothing.  Skipped, not failed, where the topology cannot be described.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import chip_smoke
from mmlspark_tpu.ops import attention_kernels as ak
from mmlspark_tpu.ops import paged_attention as pa
from mmlspark_tpu.ops import pallas_kernels as pk
from mmlspark_tpu.ops import wire_codec as wc
from mmlspark_tpu.parallel.mesh import MeshContext, make_mesh

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
FULL = chip_smoke.SIZES["full"]
LM, TRAIN, SERVE = FULL["lm"], FULL["lm_train"], FULL["lm_serve"]
VIT, FEAT = FULL["vit"], FULL["featurize"]
HEADS = LM["num_heads"]
HEAD_DIM = LM["embed_dim"] // HEADS
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def v5e():
    """MeshContext over one described v5e chip, compile cache off (such
    an executable can be written to the cache but not read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    mesh = make_mesh(devices=[topo.devices[0]])
    try:
        with MeshContext(mesh):
            yield mesh
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _shape(mesh, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, P()))


def _compile(fn, *args, **kw):
    return fn.lower(*args, **kw).compile()


def _assert_one_kernel(compiled):
    assert compiled.as_text().count("tpu_custom_call") == 1


# ---- flash attention: forward, the fused backward, the split pair ---------
def _vit_seq():
    s = (VIT["side"] // 16) ** 2          # ViT-B/16: 196 patches
    return s, ak._padded_len(s)           # ... on the 256 grid


# tag -> (B*H, S_grid, D, dtype, kv_valid, causal)
ATTN_SHAPES = {
    "lm_train_s1024_d64": (TRAIN["batch"] * HEADS, TRAIN["seq"], HEAD_DIM,
                           jnp.bfloat16, None, True),
    "vit_s256_kv196_d64": (VIT["batch"] * 12, _vit_seq()[1], 64,
                           jnp.bfloat16, _vit_seq()[0], False),
    "s1024_d128": (96, 1024, 128, jnp.bfloat16, None, True),
    # the rule in ak._kernel_d: 64-multiples run native, unpadded
    "s256_d192_native": (8, 256, 192, jnp.bfloat16, None, True),
    # f32 callers (the parity tests' dtype) at the LM cell's length
    "s1024_d64_f32": (16, 1024, 64, jnp.float32, None, True),
    # K/V stream in two major blocks forward; the fused backward at the
    # last power of two under its raised limit, asking for that VMEM
    "s8192_d64": (4, 8192, 64, jnp.bfloat16, None, True),
    # glm-train-moe's call: 256-wide heads, the fused backward asking for
    # its VMEM (20.75 MiB by the estimate)
    "glm_s4096_d256": (80, 4096, 256, jnp.bfloat16, None, True),
    # past the fused backward's limit: the dK/dV + dQ pair
    "s16384_d64_split": (4, 16384, 64, jnp.bfloat16, None, True),
}
SPLIT_ONLY = ["s16384_d64_split"]
FUSED_SHAPES = [t for t in ATTN_SHAPES if t not in SPLIT_ONLY]


def _attn_args(mesh, tag):
    bh, s, d, dtype, kv_valid, causal = ATTN_SHAPES[tag]
    q = _shape(mesh, (bh, s, d), dtype)
    stat = _shape(mesh, (bh, s), jnp.float32)
    return q, stat, causal, 1.0 / float(d) ** 0.5, kv_valid


def test_fused_backward_limit_splits_the_shapes():
    for tag, (_bh, s, d, dtype, _kv, _c) in ATTN_SHAPES.items():
        assert ak._fused_bwd_fits(s, d, jnp.dtype(dtype).itemsize) == \
            (tag not in SPLIT_ONLY), tag


@pytest.mark.parametrize("tag", list(ATTN_SHAPES))
def test_attention_forward_compiles(v5e, tag):
    q, _stat, causal, scale, kv_valid = _attn_args(v5e, tag)
    _assert_one_kernel(_compile(ak._attention_pallas, q, q, q,
                                causal, scale, kv_valid))


@pytest.mark.parametrize("tag", FUSED_SHAPES)
def test_attention_fused_backward_compiles(v5e, tag):
    """dQ, dK and dV in one kernel, the head's Q, dO and dQ resident."""
    q, stat, causal, scale, kv_valid = _attn_args(v5e, tag)
    _assert_one_kernel(_compile(ak._attention_bwd_dkdv_dq, q, q, q, q, stat,
                                stat, causal, scale, kv_valid))


@pytest.mark.parametrize("tag", list(ATTN_SHAPES))
def test_attention_dkdv_compiles(v5e, tag):
    q, stat, causal, scale, kv_valid = _attn_args(v5e, tag)
    _assert_one_kernel(_compile(ak._attention_bwd_dkdv, q, q, q, q, stat,
                                stat, causal, scale, kv_valid))


@pytest.mark.parametrize("tag", list(ATTN_SHAPES))
def test_attention_dq_compiles(v5e, tag):
    q, stat, causal, scale, kv_valid = _attn_args(v5e, tag)
    _assert_one_kernel(_compile(ak._attention_bwd_dq, q, q, q, q, stat,
                                stat, causal, scale, kv_valid))


def _serve_buckets():
    buckets = set()
    for n in SERVE["prompt_lens"]:
        b = 16
        while b < n:
            b *= 2
        buckets.add(b)
    return sorted(buckets)


@pytest.mark.parametrize("bucket", _serve_buckets())
def test_serving_prefill_attention_compiles(v5e, bucket):
    """lm_serve's admission prefill runs the f32 forward kernel at every
    prompt bucket (short S: block_q = S)."""
    q = _shape(v5e, (HEADS, bucket, HEAD_DIM), jnp.float32)
    assert ak.kernel_ok(_shape(v5e, (1, bucket, HEADS, HEAD_DIM),
                               jnp.float32))
    _assert_one_kernel(_compile(ak._attention_pallas, q, q, q, True,
                                0.125, None))


# (query heads, KV heads, q/k width, v width, window, the admission's token
# cap) of the two serving cells' attention layers, and every bucket each
# admits: a power of two, rows x bucket under the cap
ADMISSION_ATTN = {
    "laguna_full": (48, 8, 128, 128, None, 4096),
    "laguna_window": (72, 8, 128, 128, 512, 4096),
    "longcat": (64, 64, 192, 128, None, 8192),
}
ADMISSION_BUCKETS = [(kind, bucket) for kind, lo in
                     (("laguna_full", 128), ("laguna_window", 128),
                      ("longcat", 256))
                     for bucket in (128, 256, 512, 1024, 2048, 4096, 8192)
                     if lo <= bucket <= ADMISSION_ATTN[kind][5]]


@pytest.mark.parametrize("kind,bucket", ADMISSION_BUCKETS,
                         ids=[f"{k}-{b}" for k, b in ADMISSION_BUCKETS])
def test_admission_prefill_attention_compiles(v5e, kind, bucket):
    """The admission flash forward of `laguna-serve-mixed` (48 and 72
    query heads on 8 KV heads at 128, full and window 512) and
    `longcat-serve-long` (64 heads at q/k 192, v 128) at every bucket
    the cells admit, the smallest and the largest among them, and the
    most rows a program of it holds, with the prompts' lengths: what
    `prefill_attention_ok` admits by its VMEM estimate compiles under
    the limit the call asks for, K and V of a whole bucket resident, as
    ONE custom call under the name the metrics `prefill_attn_ms`,
    `prefill_attn_roofline` and `mla_prefill_roofline` find it by."""
    h, hkv, d, dv, window, cap = ADMISSION_ATTN[kind]
    rows = cap // bucket
    q = _shape(v5e, (rows, bucket, h, d), jnp.bfloat16)
    v = _shape(v5e, (rows, bucket, hkv, dv), jnp.bfloat16)
    assert ak.prefill_attention_ok(q, v)
    assert ak.prefill_attention_vmem(bucket, d, dv, h // hkv) \
        <= ak._PREFILL_VMEM_BUDGET < ak._PREFILL_VMEM_LIMIT
    block_k = ak._pick_prefill_blocks(bucket, h // hkv)[1]
    assert ak._kv_major(bucket, d, 2, block_k, dv,
                        ak._PREFILL_KV_BUDGET) == bucket
    compiled = _compile(
        ak._prefill_attention_pallas,
        _shape(v5e, (rows * h, bucket, d), jnp.bfloat16),
        _shape(v5e, (rows * hkv, bucket, d), jnp.bfloat16),
        _shape(v5e, (rows * hkv, bucket, dv), jnp.bfloat16),
        _shape(v5e, (rows,), jnp.int32), group=h // hkv, window=window,
        scale=1.0 / float(d) ** 0.5)
    _assert_one_kernel(compiled)
    assert [line.split("=")[0].strip().lstrip("%").split(".")[0]
            for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line] == \
        ["_prefill_attention_pallas"]


def test_admission_attention_shapes_are_the_cells():
    """`ADMISSION_ATTN` against the configurations the cells serve."""
    laguna = _bench_json("configs", "laguna-s-2.1.json")
    heads = laguna["num_attention_heads_per_layer"]
    assert ADMISSION_ATTN["laguna_full"][:5] == (
        heads[0], laguna["num_key_value_heads"], laguna["head_dim"],
        laguna["head_dim"], None)
    assert ADMISSION_ATTN["laguna_window"][:5] == (
        heads[1], laguna["num_key_value_heads"], laguna["head_dim"],
        laguna["head_dim"], laguna["sliding_window"])
    longcat = _bench_json("configs", "longcat-flash-chat.json")
    assert ADMISSION_ATTN["longcat"][:5] == (
        longcat["num_attention_heads"], longcat["num_attention_heads"],
        longcat["qk_nope_head_dim"] + longcat["qk_rope_head_dim"],
        longcat["v_head_dim"], None)


# ---- fused resize + normalize (featurize) ---------------------------------
_MEAN, _STD = (103.53, 116.28, 123.675), (57.375, 57.12, 58.395)
_RESIZED = [hw for hw in FEAT["sizes"] if hw != (FEAT["side"], FEAT["side"])]


@pytest.mark.parametrize("dtype", [jnp.uint8, jnp.float32],
                         ids=["uint8", "f32"])
@pytest.mark.parametrize("hw", _RESIZED, ids=lambda hw: f"{hw[0]}x{hw[1]}")
def test_resize_normalize_compiles(v5e, hw, dtype):
    h, w = hw
    side = FEAT["side"]
    assert pk._fits_vmem((1, h, w, 3), side, side, jnp.dtype(dtype).itemsize)
    consts = [_shape(v5e, c.shape, c.dtype) for c in
              pk._resize_consts(h, w, 3, side, side, _MEAN, _STD)]
    _assert_one_kernel(_compile(
        pk._fused_resize_normalize_run,
        _shape(v5e, (FEAT["batch_size"], h, w, 3), dtype), *consts,
        h_out=side, w_out=side))


def test_fused_affine_apply_compiles(v5e):
    """An ImageTransformer chain (resize + gray + normalize) composed
    into the same two-matmul kernel, 3 channels in and 1 out."""
    consts = pk.build_affine_pipeline(
        [("resize", {"height": 224, "width": 224}),
         ("colorFormat", {"format": "bgr2gray"}),
         ("normalize", {"mean": [100.0], "std": [50.0]})], 256, 256, 3)
    assert pk.affine_pipeline_fits_vmem(consts, 1)
    padded = [_shape(v5e, c.shape, c.dtype)
              for c in pk._affine_consts(*consts)]
    _assert_one_kernel(_compile(
        pk._fused_resize_normalize_run,
        _shape(v5e, (64, 256, 256, 3), jnp.uint8), *padded,
        h_out=224, w_out=224, c_out=1))


# ---- paged decode attention (lm_serve) ------------------------------------
def _paged_shapes(mesh, pool_dtype):
    b, page = SERVE["max_slots"], SERVE["page_size"]
    mp = LM["max_len"] // page
    n_pages = b * mp + 1                  # the batcher's default pool
    pool = _shape(mesh, (n_pages, page, HEADS * HEAD_DIM), pool_dtype)
    scales = _shape(mesh, (n_pages, page, HEADS), jnp.float32)
    return (b, mp, pool, scales, _shape(mesh, (b, mp), jnp.int32),
            _shape(mesh, (b,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_compiles(v5e, dtype):
    b, _mp, pool, _scales, table, pos = _paged_shapes(v5e, dtype)
    q = _shape(v5e, (b, HEADS, HEAD_DIM), dtype)
    assert pa.paged_kernel_ok(q, pool)
    _assert_one_kernel(_compile(pa._paged_pallas, q, pool, pool, table, pos))


def test_paged_attention_int8_compiles(v5e):
    b, _mp, pool, scales, table, pos = _paged_shapes(v5e, jnp.int8)
    q = _shape(v5e, (b, HEADS, HEAD_DIM), jnp.float32)
    assert pa.paged_kernel_ok(q, pool)
    _assert_one_kernel(_compile(pa._paged_pallas_int8, q, pool, scales,
                                pool, scales, table, pos))


# ---- RLE wire decoder ------------------------------------------------------
def test_rle_decoder_compiles(v5e):
    runs, n_pad = 4096, 4096 * wc.BLOCK
    assert wc.rle_kernel_ok()
    _assert_one_kernel(_compile(
        wc._pallas_decode(runs, n_pad),
        _shape(v5e, (n_pad // wc.BLOCK,), jnp.int32),
        _shape(v5e, (runs,), jnp.uint8), _shape(v5e, (runs,), jnp.int32)))


# ---- whole programs --------------------------------------------------------
def _lm_variables(mesh, model, tokens_shape):
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros(tokens_shape, jnp.int32))["params"])
    return jax.tree.map(lambda a: _shape(mesh, a.shape, a.dtype), params)


@pytest.fixture(scope="module")
def epoch_program(v5e):
    """The whole make_lm_train_epoch program at the bench width, compiled
    once for the tests that read it."""
    import optax

    from mmlspark_tpu.models.training import make_lm_train_epoch

    b, s, steps = TRAIN["batch"], TRAIN["seq"], TRAIN["steps"]
    model = chip_smoke._lm(dict(LM, max_len=s), jnp.bfloat16)
    opt = optax.adam(3e-4)
    params = _lm_variables(v5e, model, (b, s))
    opt_state = jax.eval_shape(opt.init, params)
    opt_state = jax.tree.map(lambda a: _shape(v5e, a.shape, a.dtype),
                             opt_state)
    tokens = jax.ShapeDtypeStruct(
        (steps, b, s), jnp.int32,
        sharding=NamedSharding(v5e, P(None, "data")))
    return _compile(make_lm_train_epoch(model, opt, mesh=v5e, donate=False),
                    params, opt_state, tokens)


# lm-serve-closed's own pools (benchmarks/configs/gpt2-medium.json under
# workloads/lm-serve-closed.json): 32 slots x 16 pages of 64 + the trash
# page, 16 heads of 64, bf16.  Two layers show what every layer does.
CELL_LM = dict(vocab_size=8192, embed_dim=1024, num_layers=2, num_heads=16,
               max_len=1024)
CELL_SERVE = dict(max_slots=32, page_size=64)
# (LM widths, serving sizes, dtype): chip_smoke's full sizes and the cell's
POOL_PROGRAMS = {"smoke": (LM, SERVE, jnp.float32),
                 "cell": (CELL_LM, CELL_SERVE, jnp.bfloat16)}


def _step_inputs(mesh, slots, stats):
    """What the batcher's decode step takes between the cache and the
    tables (serving/batcher.py, ONE STEP AHEAD): the step before's output
    vector and positions, the host's [2, slots] override, the admissions'
    first tokens."""
    return (_shape(mesh, (slots + stats,), jnp.int32),
            _shape(mesh, (slots,), jnp.int32),
            _shape(mesh, (2, slots), jnp.int32),
            _shape(mesh, (slots,), jnp.int32))


def _batcher_programs(mesh, lm, serve, dtype, load_rows=2):
    """The batcher's OWN decode step and page-load programs (its jits,
    with their donation), compiled for the described chip at the pools'
    real shape.  The batcher is built over a two-page pool, so nothing
    of size is allocated here: the programs are lowered from shapes.
    -> (decode step, load program, the pools' bytes, the pool's shape
    as the compiled text writes it)."""
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    model = chip_smoke._lm(lm, dtype)
    variables = {"params": jax.tree.map(
        lambda a: _shape(mesh, a.shape, dtype),
        _lm_variables(mesh, model, (1, 8)))}
    b, page = serve["max_slots"], serve["page_size"]
    mp = lm["max_len"] // page
    batcher = ContinuousBatcher(model, variables, max_slots=b, paged=True,
                                page_size=page, num_pages=2)
    n_pages = b * mp + 1                  # the batcher's default pool
    cache = jax.tree.map(
        lambda a: _shape(mesh, (n_pages, *a.shape[1:]), a.dtype),
        batcher._cache)
    step = _compile(batcher._step, variables, cache,
                    *_step_inputs(mesh, b, 0),
                    (_shape(mesh, (b, mp), jnp.int32),))
    heads, head_dim = lm["num_heads"], lm["embed_dim"] // lm["num_heads"]
    rows = jax.tree.map(
        lambda a: _shape(mesh, (load_rows, lm["max_len"], heads, head_dim),
                         a.dtype), batcher._cache)
    load = _compile(batcher._load_paged_many, cache, rows,
                    _shape(mesh, (load_rows * mp,), jnp.int32))
    pools = jax.tree.leaves(cache)
    hlo = {"float32": "f32", "bfloat16": "bf16"}[jnp.dtype(dtype).name]
    return (step, load, sum(a.size * a.dtype.itemsize for a in pools),
            f"{hlo}[{','.join(map(str, pools[0].shape))}]")


@pytest.fixture(scope="module")
def pool_programs(v5e):
    """name -> _batcher_programs(...), compiled on first use."""
    made = {}

    def get(name):
        if name not in made:
            made[name] = _batcher_programs(v5e, *POOL_PROGRAMS[name])
        return made[name]

    return get


@pytest.fixture(scope="module")
def split_backward_program(v5e):
    """Forward and backward of one attention call past the fused
    backward's limit, through `fused_attention`'s own two halves: the
    dK/dV + dQ pair under their names."""
    bh, s, d, dtype, _kv_valid, causal = ATTN_SHAPES[SPLIT_ONLY[0]]
    q = _shape(v5e, (1, s, bh, d), dtype)

    def both(q, k, v, g):
        out, lse = ak._run_kernel(q, k, v, causal)
        return ak._flash_bwd(q, k, v, out, lse, g, causal)

    return _compile(jax.jit(both), q, q, q, q)


@pytest.fixture(scope="module")
def decode_program(pool_programs):
    """The batcher's slot-decode program (TransformerLM.decode_step over
    page pools) at chip_smoke's full sizes."""
    return pool_programs("smoke")[0]


# the same program's temp_size_in_bytes at the parent of PR 30 (f32 `out`
# residuals and f32 dq/dk/dv; a compile fact, PR 30)
EPOCH_TEMP_BYTES_BEFORE_PR30 = 7_352_914_432


def test_lm_train_epoch_compiles_with_24_kernels(epoch_program):
    """12 layers x (forward, fused backward) custom calls, it fits one
    chip, and it keeps no more than it did with three kernels a layer
    writing f32."""
    assert epoch_program.as_text().count("tpu_custom_call") == \
        2 * LM["num_layers"] == 24
    mem = epoch_program.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    assert mem.temp_size_in_bytes <= EPOCH_TEMP_BYTES_BEFORE_PR30


def test_paged_decode_step_compiles_with_12_kernels(decode_program):
    """One page-walk kernel per layer."""
    assert decode_program.as_text().count("tpu_custom_call") == \
        LM["num_layers"]


@pytest.mark.parametrize("program", ["decode_step", "load_paged_many"])
@pytest.mark.parametrize("sizes", list(POOL_PROGRAMS))
def test_pool_programs_update_the_pools_in_place(pool_programs, sizes,
                                                 program):
    """The mechanism's counter, and it is static: the page pools keep the
    layout their consumers read, and are donated.  So no program that
    takes the pools holds a `copy` whose result has a pool's shape (a
    [NP, page, H, D] pool was relaid four times a layer: to row-major
    for the scatter and the page walk, and back), and every byte of the
    pools is aliased from argument to result."""
    import re

    step, load, pool_bytes, pool_shape = pool_programs(sizes)
    compiled = step if program == "decode_step" else load
    copies = [line.strip()[:160] for line in compiled.as_text().splitlines()
              if re.search(r"= " + re.escape(pool_shape)
                           + r"\S* copy(-start)?\(", line)]
    assert copies == []
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
    if program == "decode_step":
        assert compiled.as_text().count("tpu_custom_call") == \
            POOL_PROGRAMS[sizes][0]["num_layers"]


def _assert_runs_one_step_ahead(compiled, slots, stats, vocab):
    """serving/batcher.py, ONE STEP AHEAD, as the compiled decode program
    shows it: it takes the step before's tokens and positions, selects
    between them and what the host gave itself, and hands back the tokens
    it chose (with `stats` statistics behind them) and the next
    positions, never [slots, vocab] logits: nothing of the tick is left
    to a second program."""
    import re

    (_variables, _cache, before, pos, ovr, adm, _tables), _kw = \
        compiled.args_info
    assert (before.shape, pos.shape, ovr.shape, adm.shape) == \
        ((slots + stats,), (slots,), (2, slots), (slots,))
    chosen, pos_next, _pools = compiled.out_info
    assert (chosen.shape, chosen.dtype) == ((slots + stats,), jnp.int32)
    assert (pos_next.shape, pos_next.dtype) == ((slots,), jnp.int32)
    assert not [o.shape for o in jax.tree.leaves(compiled.out_info)
                if o.shape[-1:] == (vocab,)]
    assert re.search(rf"= s32\[{slots}\]\S* select\(", compiled.as_text())


@pytest.mark.parametrize("sizes", list(POOL_PROGRAMS))
def test_decode_program_picks_and_feeds_its_own_tokens(pool_programs, sizes):
    lm, serve, _dtype = POOL_PROGRAMS[sizes]
    _assert_runs_one_step_ahead(pool_programs(sizes)[0], serve["max_slots"],
                                0, lm["vocab_size"])


# ---- Laguna-S-2.1: decode step, one admission, the page load ---------------
# benchmarks/configs/laguna-s-2.1.json at its published widths and its
# share (128 experts held, 50,176 vocabulary rows), cut to TWO layers:
# layer 0 (dense MLP, full attention, 48 heads) and layer 1 (routed
# experts, window attention, 72 heads) show both kinds of pool, both page
# walks and the grouped matmul.  laguna-serve-mixed's own pools: 32 slots,
# pages of 64, 4,096 positions (64 pages a slot; a ring of 9 in the window
# kind).
LAGUNA_SERVE = dict(max_slots=32, page_size=64, context=4096)
LAGUNA_ADMISSION = (4, 512)            # rows x bucket of one admission


@pytest.fixture(scope="module")
def laguna_programs(v5e):
    """-> {"decode_step", "admission", "load"} compiled for the described
    chip, the pools' bytes and their shapes as the compiled text writes
    them."""
    import json

    from mmlspark_tpu.models.moe_lm import MoELM
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    with open(os.path.join(BENCH, "configs", "laguna-s-2.1.json")) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    b, page, ctx = (LAGUNA_SERVE[k] for k in ("max_slots", "page_size",
                                              "context"))
    model = MoELM.from_config(cfg, ctx, jnp.bfloat16)
    variables = {"params": _lm_variables(v5e, model, (1, 8))}
    batcher = ContinuousBatcher(model, variables, max_slots=b, paged=True,
                                page_size=page, num_pages=2)
    mp, ring = ctx // page, batcher._win.ring
    assert ring == cfg["sliding_window"] // page + 1 == 9
    pages = (b * mp + 1, batcher._win.np)
    cache = tuple(
        tuple(_shape(v5e, (pages[kind], page, model.kv_width), jnp.bfloat16)
              for _ in range(2)) for kind in batcher._layer_kinds)
    tables = (_shape(v5e, (b, mp), jnp.int32),
              _shape(v5e, (b, ring), jnp.int32))
    k, bucket = LAGUNA_ADMISSION
    rows = tuple(tuple(_shape(v5e, (k, bucket, model.kv_width), jnp.bfloat16)
                       for _ in range(2)) for _ in batcher._layer_kinds)
    ids = tuple(_shape(v5e, (k * bucket // page,), jnp.int32)
                for _ in range(2))
    pools = jax.tree.leaves(cache)
    # what `teacher_force` runs: the same functions handing back logits
    # and the routed layers' taps, a whole-context prompt admitted alone
    replay_step, replay_admission = batcher._own_programs(taps=True)
    # the tokens lead the step's output vector, the model's statistics
    # follow them: the next step takes the whole vector
    given = _step_inputs(v5e, b, len(batcher._stat_counters))
    adm = _shape(v5e, (b,), jnp.int32)
    return {
        "replay_step": _compile(replay_step, variables, cache, *given,
                                tables),
        "replay_admission": _compile(
            replay_admission, variables, _shape(v5e, (1, ctx), jnp.int32),
            _shape(v5e, (1,), jnp.int32), _shape(v5e, (1,), jnp.int32), adm,
            _shape(v5e, (), jnp.int32)),
        "decode_step": _compile(batcher._step, variables, cache, *given,
                                tables),
        "admission": _compile(
            batcher._prefill_last, variables,
            _shape(v5e, (k, bucket), jnp.int32), _shape(v5e, (k,), jnp.int32),
            _shape(v5e, (k,), jnp.int32), adm),
        "load": _compile(batcher._load_kinds, cache, rows, ids),
        "pool_bytes": sum(a.size * a.dtype.itemsize for a in pools),
        "pool_shapes": [f"bf16[{n},{page},{model.kv_width}]" for n in pages],
        "stats": batcher._stat_counters,
    }


@pytest.fixture(scope="module")
def laguna_decode_program(laguna_programs):
    return laguna_programs["decode_step"]


@pytest.fixture(scope="module")
def laguna_admission_program(laguna_programs):
    return laguna_programs["admission"]


@pytest.mark.parametrize("program", ["decode_step", "load"])
def test_laguna_pool_programs_update_the_pools_in_place(laguna_programs,
                                                        program):
    """Both kinds of pool are donated and keep their layout: every byte
    aliased from argument to result, no `copy` of a pool's shape."""
    import re

    compiled = laguna_programs[program]
    text = compiled.as_text()
    for shape in laguna_programs["pool_shapes"]:
        assert [line.strip()[:160] for line in text.splitlines()
                if re.search(r"= " + re.escape(shape)
                             + r"\S* copy(-start)?\(", line)] == []
    assert compiled.memory_analysis().alias_size_in_bytes == \
        laguna_programs["pool_bytes"]


@pytest.mark.parametrize("program,calls", [("decode_step", 4),
                                           ("admission", 4),
                                           ("replay_step", 4),
                                           ("replay_admission", 4)])
def test_laguna_programs_carry_their_kernels(laguna_programs, program,
                                             calls):
    """Two attention calls (page walks, or the prefill flash forward) and
    the sparse layer's two grouped matmuls; and the admission, which makes
    no [rows, bucket, vocabulary] logits, fits the chip beside the whole
    share's weights.  The programs `teacher_force` replays with are the
    same calls with more handed back."""
    compiled = laguna_programs[program]
    assert compiled.as_text().count("tpu_custom_call") == calls
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5e9
    if program == "admission":
        k, _bucket = LAGUNA_ADMISSION
        assert f"f32[{k},50176]" in compiled.as_text()


def test_laguna_decode_program_picks_and_feeds_its_own_tokens(
        laguna_programs):
    _assert_runs_one_step_ahead(
        laguna_programs["decode_step"], LAGUNA_SERVE["max_slots"],
        len(laguna_programs["stats"]), 50176)


# ---- LongCat-Flash-Chat: latent page walk, 192/128 flash forward ------------
LONGCAT_SERVE = {"max_slots": 32, "page_size": 64, "context": 8192}
LONGCAT_ADMISSION = (1, 8192)          # the largest: a whole context alone
V5E_BYTES_LIMIT = 16.9e9               # what the runtime leaves a program


@pytest.fixture(scope="module")
def longcat_programs(v5e):
    """-> {"decode_step", "admission", "load"} compiled for the described
    chip at 2 blocks of the published widths (4 cached sublayers), with
    the pools' bytes and shape."""
    import json

    from mmlspark_tpu.models.longcat_lm import LongCatLM
    from mmlspark_tpu.serving.batcher import ContinuousBatcher

    with open(os.path.join(BENCH, "configs", "longcat-flash-chat.json")) as f:
        cfg = dict(json.load(f), num_layers=2)
    b, page, ctx = (LONGCAT_SERVE[k] for k in ("max_slots", "page_size",
                                               "context"))
    model = LongCatLM.from_config(cfg, ctx, jnp.bfloat16)
    variables = {"params": _lm_variables(v5e, model, (1, 8))}
    batcher = ContinuousBatcher(model, variables, max_slots=b, paged=True,
                                page_size=page, num_pages=2)
    assert batcher._layer_kinds == (0,) * 4 and batcher._win is None
    mp, (width,) = ctx // page, model.cache_rows[0]
    assert width == 640                 # 576 values in whole lane tiles
    n_pages = b * mp + 1
    cache = tuple((_shape(v5e, (n_pages, page, width), jnp.bfloat16),)
                  for _ in batcher._layer_kinds)
    k, bucket = LONGCAT_ADMISSION
    rows = tuple((_shape(v5e, (k, bucket, width), jnp.bfloat16),)
                 for _ in batcher._layer_kinds)
    given = _step_inputs(v5e, b, len(batcher._stat_counters))
    pools = jax.tree.leaves(cache)
    params = jax.tree.leaves(variables)
    admitted = (variables, _shape(v5e, (k, bucket), jnp.int32),
                _shape(v5e, (k,), jnp.int32), _shape(v5e, (k,), jnp.int32),
                _shape(v5e, (b,), jnp.int32))
    # what `teacher_force` runs for the cell's `verify`: the taps it asks
    # for, a piece of positions a program
    _step, replay_admission = batcher._own_programs(
        ("router_input", "logits", "experts", "routed", "mla_query",
         "mla_q_nope"))
    return {
        "decode_step": _compile(batcher._step, variables, cache, *given,
                                (_shape(v5e, (b, mp), jnp.int32),)),
        "admission": _compile(batcher._prefill_last, *admitted),
        "replay_admission": _compile(replay_admission, *admitted,
                                     _shape(v5e, (), jnp.int32)),
        "load": _compile(batcher._load_kinds, cache, rows,
                         (_shape(v5e, (k * bucket // page,), jnp.int32),)),
        "pool_bytes": sum(a.size * a.dtype.itemsize for a in pools),
        "param_bytes": sum(a.size * a.dtype.itemsize for a in params),
        "pool_shape": f"bf16[{n_pages},{page},{width}]",
        "stats": batcher._stat_counters,
    }


@pytest.fixture(scope="module")
def longcat_decode_program(longcat_programs):
    return longcat_programs["decode_step"]


@pytest.fixture(scope="module")
def longcat_admission_program(longcat_programs):
    return longcat_programs["admission"]


@pytest.mark.parametrize("program", ["decode_step", "load"])
def test_longcat_pool_programs_update_the_pools_in_place(longcat_programs,
                                                         program):
    """The one-pool kind is donated and keeps its layout: every byte
    aliased from argument to result, no `copy` of the pool's shape (a
    row of 576 would be padded to 640 by the layout and relaid for the
    page walk's DMAs: the pool is 640 wide to begin with)."""
    import re

    compiled = longcat_programs[program]
    assert [line.strip()[:160] for line in compiled.as_text().splitlines()
            if re.search(r"= " + re.escape(longcat_programs["pool_shape"])
                         + r"\S* copy(-start)?\(", line)] == []
    assert compiled.memory_analysis().alias_size_in_bytes == \
        longcat_programs["pool_bytes"]


@pytest.mark.parametrize("program", ["decode_step", "admission",
                                     "replay_admission"])
def test_longcat_programs_carry_their_kernels(longcat_programs, program):
    """A block is two latent attentions (page walks, or the flash forward
    at 192/128) and the routed layer's two grouped matmuls: 8 custom
    calls at 2 blocks.  And the largest admission fits the chip beside
    the 4-block share's weights and 8 sublayers of pools."""
    compiled = longcat_programs[program]
    assert compiled.as_text().count("tpu_custom_call") == 8
    mem = compiled.memory_analysis()
    embed_head = 2 * 16384 * 6144 * 2
    share = 2 * (longcat_programs["param_bytes"] - embed_head) + embed_head
    assert round(share / 1e9, 2) == 10.35
    # at 2 blocks; the 4-block programs' temporaries are in PERF.md
    handed_back = (2 * mem.output_size_in_bytes
                   if program == "replay_admission" else 0)
    assert (share + 2 * longcat_programs["pool_bytes"]
            + mem.temp_size_in_bytes + handed_back) < V5E_BYTES_LIMIT - 1e9
    if program != "decode_step":
        assert "f32[1,16384]" in compiled.as_text()
    if program == "replay_admission":
        # each layer's taps are cut before they are stacked: the replay
        # needs the served program's temporaries and no whole context's
        # taps beside them (they were a quarter more, and `verify` did
        # not fit beside pools at dense parity)
        served = longcat_programs["admission"].memory_analysis()
        assert mem.temp_size_in_bytes < 1.06 * served.temp_size_in_bytes


def test_longcat_decode_program_picks_and_feeds_its_own_tokens(
        longcat_programs):
    _assert_runs_one_step_ahead(
        longcat_programs["decode_step"], LONGCAT_SERVE["max_slots"],
        len(longcat_programs["stats"]), 16384)


# ---- glm-train-moe: the cell's own epoch program ---------------------------
def _bench_json(*parts):
    import json

    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


GLM_LAYERS = 5 + 1        # attention sublayers: the kept layers and the MTP block
GLM_SPARSE = 4 + 1        # routed layers among them


@pytest.fixture(scope="module")
def glm_epoch_program(v5e):
    """make_lm_train_epoch over GlmMoeLM at benchmarks/configs/
    glm-4.7-flash.json, the batch and steps of workloads/glm-train-moe.json
    and the sequence of its traffic file: what the cell times, compiled
    once for the tests that read it (about a minute)."""
    import optax

    from mmlspark_tpu.models.glm_moe_lm import GlmMoeLM
    from mmlspark_tpu.models.training import make_lm_train_epoch

    cfg = _bench_json("configs", "glm-4.7-flash.json")
    cell = _bench_json("workloads", "glm-train-moe.json")["params"]
    seq = _bench_json("traffic", "uniform-tokens-s4096.json")["seq_len"]
    model = GlmMoeLM.from_config(cfg, seq)
    opt = optax.adam(cell["learning_rate"])
    variables = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    variables = jax.tree.map(
        lambda a: _shape(v5e, a.shape, a.dtype),
        {k: variables[k] for k in ("params", "controller")})
    opt_state = jax.tree.map(lambda a: _shape(v5e, a.shape, a.dtype),
                             jax.eval_shape(opt.init, variables["params"]))
    tokens = jax.ShapeDtypeStruct(
        (cell["steps_per_epoch"], cell["batch"], seq), jnp.int32,
        sharding=NamedSharding(v5e, P(None, "data")))
    return _compile(make_lm_train_epoch(model, opt, mesh=v5e), variables,
                    opt_state, tokens)


def test_glm_epoch_program_fits_with_its_kernels(glm_epoch_program):
    """The cell's batch is the largest of 1, 2, 4 that fits 14.5 GB by
    this number (`batch_found` in its workload file: 13.08 GB); every
    attention sublayer runs the flash forward twice (a checkpointed
    block) and the one-kernel backward, every routed layer the grouped
    matmul's forward twice (two calls each) and its dX (two) and dW
    (three) programs."""
    import collections
    import re

    mem = glm_epoch_program.memory_analysis()
    held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert held < 14.5e9
    assert mem.argument_size_in_bytes > 8.4e9      # weights and Adam state
    names = collections.Counter(
        re.sub(r"[.\d]+$", "", m.group(1)) for m in re.finditer(
            r'%(\S+) = [^\n]*custom_call_target="tpu_custom_call"',
            glm_epoch_program.as_text()))
    assert names == {"_attention_pallas": 2 * GLM_LAYERS,
                     "_attention_bwd_dkdv_dq": GLM_LAYERS,
                     "_moe_gmm_train_fwd": 4 * GLM_SPARSE,
                     "_moe_gmm_bwd_dx": 2 * GLM_SPARSE,
                     "_moe_gmm_bwd_dw": 3 * GLM_SPARSE}


# what `moe_rows_ms` counts in this cell, a routed layer a step: the jitted
# program an operation came from (the compiled text says; the trace does
# not) and how many.  Six gathers (rows in: forward and again under the
# checkpoint; rows out: forward; the three transposes, written as gathers),
# the weight's multiply in `combine`'s transpose, and the pass that zeroes
# the unused rows' gradient inside `_moe_gmm_bwd_dx`.
GLM_ROW_OPS = {"_moe_rows_gather": 2, "_moe_rows_combine": 1,
               "_moe_rows_combine_bwd": 3, "_moe_rows_gather_bwd": 1,
               "_moe_gmm_bwd_dx": 1}


def test_row_buffer_pattern_finds_the_gathers(glm_epoch_program,
                                              bench_trace_lib):
    """`moe_rows_ms` in this cell: XLA names the row buffers' fusions by
    kind, so the cell's pattern goes by their shapes.  The shapes are
    the cell's batch's, and what the pattern finds among the operations
    a trace would show (the instructions outside fused computations) is
    exactly `GLM_ROW_OPS`: nothing else of the step has those shapes."""
    import collections
    import re

    tr = bench_trace_lib
    cfg = _bench_json("configs", "glm-4.7-flash.json")
    cell = _bench_json("workloads", "glm-train-moe.json")
    seq = _bench_json("traffic", "uniform-tokens-s4096.json")["seq_len"]
    over = cell["per_layer"]["moe_rows_ms"]
    assigned = cell["params"]["batch"] * seq * cfg["num_experts_per_tok"]
    buffer = (-(-assigned // 128) + cfg["n_routed_experts"]) * 128
    assert over["pattern"] == (
        rf"\[({buffer}|{assigned}),{cfg['hidden_size']}\]")
    text = glm_epoch_program.as_text()
    fused = set(re.findall(r"calls=%?([\w.\-]+)", text))
    events, inside = [], False
    for line in text.splitlines():
        if line and not line.startswith(" "):       # a computation opens
            name = line.split(" ")[1 if line.startswith("ENTRY") else 0]
            inside = name.lstrip("%") in fused
        elif " = " in line and not inside:
            op, detail = tr.op_name(line.strip().removeprefix("ROOT "))
            source = re.findall(r"jit\((_moe_\w+)\)", line)
            events.append((op, 0.0, 1.0, detail, source[-1] if source
                           else line.strip()[:120]))
    found = {op for op, _s, _e in tr.ops(
        [e[:4] for e in events], over["pattern"], over["exclude"])}
    assert len(found) == sum(GLM_ROW_OPS.values()) * GLM_SPARSE
    sources = collections.Counter(e[4] for e in events if e[0] in found)
    assert sources == {k: n * GLM_SPARSE for k, n in GLM_ROW_OPS.items()}
    assert _metric_pattern("moe_rows_ms") == "^_moe_rows_"


def _metric_pattern(name):
    return _bench_json("metrics", name + ".json")["args"]["pattern"]


# (program fixture, metric file, kernel, custom calls the pattern must find)
KERNEL_NAMES = [
    # every training kernel of the epoch program: the forward and the
    # fused backward, whose name the one pattern finds by its prefix
    ("epoch_program", "flash_attn_ms", "_attention_pallas",
     LM["num_layers"]),
    ("epoch_program", "flash_attn_ms", "_attention_bwd_dkdv_dq",
     LM["num_layers"]),
    ("epoch_program", "flash_attn_roofline", "_attention_pallas",
     LM["num_layers"]),
    ("epoch_program", "flash_attn_roofline", "_attention_bwd_dkdv_dq",
     LM["num_layers"]),
    # ... and the split pair a sequence past the fused limit takes
    ("split_backward_program", "flash_attn_ms", "_attention_pallas", 1),
    ("split_backward_program", "flash_attn_ms", "_attention_bwd_dkdv", 1),
    ("split_backward_program", "flash_attn_roofline", "_attention_bwd_dq",
     1),
    ("decode_program", "paged_attn_ms", "_paged_pallas", LM["num_layers"]),
    # Laguna's two layers: one full and one window page walk, and the gate/up
    # and down calls of the one sparse layer's grouped matmul
    ("laguna_decode_program", "paged_attn_full_ms", "_paged_gqa_full", 1),
    ("laguna_decode_program", "paged_attn_window_ms", "_paged_gqa_window", 1),
    ("laguna_decode_program", "paged_attn_roofline", "_paged_gqa_full", 1),
    ("laguna_decode_program", "paged_attn_roofline", "_paged_gqa_window", 1),
    ("laguna_decode_program", "moe_expert_ms", "_moe_gmm_decode", 2),
    ("laguna_decode_program", "moe_expert_roofline", "_moe_gmm_decode", 2),
    ("laguna_decode_program", "moe_expert_decode_ms", "_moe_gmm_decode", 2),
    # the admission: the same two calls at the MXU's row tile, and each
    # layer's flash forward
    ("laguna_admission_program", "moe_expert_ms", "_moe_gmm_prefill", 2),
    ("laguna_admission_program", "moe_expert_roofline", "_moe_gmm_prefill", 2),
    ("laguna_admission_program", "moe_expert_prefill_ms", "_moe_gmm_prefill",
     2),
    ("laguna_admission_program", "prefill_attn_ms",
     "_prefill_attention_pallas", 2),
    ("laguna_admission_program", "prefill_attn_roofline",
     "_prefill_attention_pallas", 2),
    # LongCat's two blocks: two latent page walks and the routed layer's
    # gate/up and down calls a block; the admission's flash forward at
    # q/k 192 and v 128 keeps the kernel's name
    ("longcat_decode_program", "mla_decode_ms", "_paged_mla", 4),
    ("longcat_decode_program", "mla_decode_roofline", "_paged_mla", 4),
    ("longcat_decode_program", "moe_roofline_family", "_moe_gmm_decode", 4),
    ("longcat_decode_program", "moe_expert_decode_ms", "_moe_gmm_decode", 4),
    ("longcat_admission_program", "mla_prefill_roofline",
     "_prefill_attention_pallas", 4),
    ("longcat_admission_program", "prefill_attn_ms",
     "_prefill_attention_pallas", 4),
    ("longcat_admission_program", "moe_roofline_family", "_moe_gmm_prefill",
     4),
    ("longcat_admission_program", "moe_expert_prefill_ms", "_moe_gmm_prefill",
     4),
    # the trained routed model: the flash kernels at 256-wide heads keep
    # their names (forward twice a sublayer, the one-kernel backward), and
    # `^_moe_gmm` finds the grouped matmul's three training programs
    ("glm_epoch_program", "flash_attn_ms", "_attention_pallas",
     2 * GLM_LAYERS),
    ("glm_epoch_program", "flash_attn_ms", "_attention_bwd_dkdv_dq",
     GLM_LAYERS),
    ("glm_epoch_program", "flash_attn_roofline_family",
     "_attention_bwd_dkdv_dq", GLM_LAYERS),
    ("glm_epoch_program", "moe_train_ms", "_moe_gmm_train_fwd",
     4 * GLM_SPARSE),
    ("glm_epoch_program", "moe_train_ms", "_moe_gmm_bwd_dx", 2 * GLM_SPARSE),
    ("glm_epoch_program", "moe_train_roofline", "_moe_gmm_bwd_dw",
     3 * GLM_SPARSE),
]


@pytest.mark.parametrize("program,metric,kernel,calls", KERNEL_NAMES,
                         ids=[f"{m}-{k}" for _p, m, k, _c in KERNEL_NAMES])
def test_metric_patterns_find_the_kernels(request, bench_trace_lib, program,
                                          metric, kernel, calls):
    """The names the benchmark's trace metrics lean on.  The TPU runtime
    names a device event by its whole HLO instruction, and the compiler
    keeps the jitted Python function's name as the custom call's
    instruction name (`%_paged_pallas.12 = ... custom-call(...)`), so the
    compiled text stands in for the trace here: each tpu_custom_call
    instruction goes through the reducers' own `op_name` and `ops`, and
    the `pattern` of the metric file has to find every call of the kernel.
    A rename of a kernel fails here, and not as a metric gone silent on
    the chip.  (The patterns were written against the chip's trace, whose
    form benchmarks/fixtures/lm-train-v5e.trace.json.gz shows; both
    flash metric files carry one pattern.)"""
    tr = bench_trace_lib
    text = request.getfixturevalue(program).as_text()
    events = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name, detail = tr.op_name(line.strip())
            events.append((name, 0.0, 1.0, detail))
    assert _metric_pattern("flash_attn_ms") == \
        _metric_pattern("flash_attn_roofline")
    found = [n for n, _s, _e in tr.ops(events, _metric_pattern(metric))]
    mine = [n for n in found if n.split(".")[0] == kernel]
    assert len(mine) == calls, (kernel, sorted({n.split(".")[0]
                                                for n, *_ in events}))
    if metric.startswith("flash_attn"):
        # ... and the pattern misses no attention kernel of the program
        assert len(found) == sum(n.startswith("_attention")
                                 for n, *_ in events)


def test_described_context_does_not_leak(v5e):
    """Inside the described-device MeshContext the kernels compile for
    the chip; a nested context over the attached CPU devices returns to
    interpret mode — and the SAME shape that compiled for the chip above
    still runs there (the jit cache keys on the mesh context, so the two
    traces never meet)."""
    bh, s, d, dtype, kv_valid, causal = ATTN_SHAPES["s256_d192_native"]
    assert pk.on_tpu()
    with MeshContext(make_mesh()):
        assert not pk.on_tpu()
        q = jnp.ones((bh, s, d), dtype)
        out, _lse = ak._attention_pallas(q, q, q, causal,
                                         1.0 / float(d) ** 0.5, kv_valid)
        assert np.isfinite(np.asarray(out, np.float32)).all()
