"""The Pallas kernels and the Xception kernel program COMPILE for the
chip — checked without one.

The TPU compiler is installed next to JAX and compiles for a chip that
is described (``jax.experimental.topologies``) rather than attached, so
Mosaic's refusals — a slice off the tiling, a block over the VMEM
budget — fail here, in tier-1, instead of in the first chip run.
Interpret-mode parity (``tests/test_ops_sepconv.py``) cannot see them.

The shapes are not guessed: Xception is traced abstractly with the
kernel entry point replaced by a recorder, so the cases ARE the calls
the zoo makes.  Nothing runs, so nothing here says anything about
results or speed.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import sepconv

BATCH = 32  # the audited serving plan's largest bucket

_KERNELS = ("_fused_sepconv_tpu",)


def _trace_kernel_calls(module, input_hw):
    """Every (kernel, arg shapes, static kwargs) ``module`` reaches in one
    abstract inference trace, de-duplicated, in call order."""
    calls = []

    def recorder(name):
        def record(xf, dwk, pw, a, b, *static, **kw):
            kw.pop("interpret", None)
            key = (name, tuple((t.shape, str(t.dtype))
                               for t in (xf, dwk, pw, a, b)),
                   static, tuple(sorted(kw.items())))
            if key not in calls:
                calls.append(key)
            return jnp.zeros(xf.shape[:2] + (pw.shape[-1],), jnp.bfloat16)
        return record

    saved = {k: getattr(sepconv, k) for k in _KERNELS + ("_on_tpu",)}
    try:
        for k in _KERNELS:
            setattr(sepconv, k, recorder(k))
        sepconv._on_tpu = lambda: True
        h, w = input_hw
        x = jax.ShapeDtypeStruct((BATCH, h, w, 3), jnp.float32)
        variables = jax.eval_shape(
            lambda r, xb: module.init(r, xb, train=False),
            jax.random.PRNGKey(0), x)
        jax.eval_shape(lambda v, xb: module.apply(v, xb, train=False),
                       variables, x)
    finally:
        for k, v in saved.items():
            setattr(sepconv, k, v)
    return calls


def _zoo_kernel_cases():
    """Each distinct shape of Xception's kernel calls, at both input
    dtypes a call can bring: float32 (the model's activations at the
    engine's default) and bfloat16 (a kernel's own output, or the
    activations under a bfloat16 compute dtype)."""
    from sparkdl_tpu.models.xception import Xception

    cases = []
    for name, shapes, static, kw in _trace_kernel_calls(
            Xception(fused_inference=True), (299, 299)):
        (xf_shape, _), rest = shapes[0], shapes[1:]
        for dtype in ("float32", "bfloat16"):
            case = (name, ((xf_shape, dtype),) + rest, static, kw)
            if case not in cases:
                cases.append(case)
    return cases


def _case_id(case):
    name, shapes, static, kw = case
    ((n, lo, c), dtype), f = shapes[0], shapes[2][0][-1]
    tail = "-".join(str(v) for v in static + tuple(v for _, v in kw))
    return f"{name.strip('_')}-{dtype}-lo{lo}-c{c}-f{f}-{tail}"


KERNEL_CASES = _zoo_kernel_cases()


@pytest.fixture(scope="module")
def v5e_chip():
    """One described v5e chip; the persistent compile cache is turned off
    around these compiles (an executable compiled for a described chip is
    written to it but cannot be read back without the chip — the next run
    would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"v5e topology cannot be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_enumeration_covers_every_kernel():
    """The recorder saw the kernel at Xception's 6 distinct shapes, each
    at two input dtypes (a model edit that drops the kernel path would
    silently shrink the parametrisation below)."""
    names = [c[0] for c in KERNEL_CASES]
    assert set(names) == set(_KERNELS)
    assert len(KERNEL_CASES) == 12


@pytest.mark.parametrize("case", KERNEL_CASES, ids=_case_id)
def test_kernel_compiles_for_v5e(case, v5e_chip):
    name, shapes, static, kw = case
    args = [jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=v5e_chip)
            for shape, dtype in shapes]
    compiled = getattr(sepconv, name).lower(*args, *static,
                                            **dict(kw)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_xception_kernel_program_compiles_for_v5e(v5e_chip, monkeypatch):
    """The whole featurize program DeepImageFeaturizer serves on one
    chip (``zoo_model_fn`` + ``build_dispatch_jit``, kernel path on) at
    b32 f32: it compiles, holds the kernels, and fits the chip's 16 GB
    with room to spare."""
    from jax.sharding import Mesh

    from sparkdl_tpu.models import get_model_spec
    from sparkdl_tpu.models.xception import Xception
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit
    from sparkdl_tpu.transformers.named_image import zoo_model_fn

    # auto mode asks the attached backend, which here is the CPU: steer
    # the probe and pin the module to what one TPU chip resolves
    monkeypatch.setattr(sepconv, "_on_tpu", lambda: True)
    fn = zoo_model_fn("Xception", featurize=True,
                      module=Xception(fused_inference=True))
    device = next(iter(v5e_chip.device_set))
    mesh = Mesh(np.asarray([device]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    spec = get_model_spec("Xception")
    h, w = spec.input_size
    compiled = build_dispatch_jit(fn, mesh, donate_batch=False).lower(
        spec.abstract_variables(),
        jax.ShapeDtypeStruct((BATCH, h, w, 3), np.uint8)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 20
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 8 * 2**30


# -- the hybrid trunk's two kernels at the published widths ------------------

def _on_chip(shape, dtype, chip):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def test_ssd_scan_compiles_for_v5e_at_published_widths(v5e_chip):
    """``ssd_scan`` at a dispatch of ``falcon_h1_34b.rows4k``: 8 rows of
    4096 positions, 32 heads of 128 in 2 groups, state 256, chunk 128."""
    from sparkdl_tpu.ops import ssd

    rows, t, heads, p, groups, n = 8, 4096, 32, 128, 2, 256
    bf16, f32 = jnp.bfloat16, jnp.float32
    compiled = ssd.ssd_scan_kernel.lower(
        _on_chip((rows, t, heads, p), bf16, v5e_chip),
        _on_chip((rows, t, heads), f32, v5e_chip),
        _on_chip((heads,), f32, v5e_chip),
        _on_chip((rows, t, groups, n), bf16, v5e_chip),
        _on_chip((rows, t, groups, n), bf16, v5e_chip),
        _on_chip((heads,), f32, v5e_chip), chunk=128).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the trace knows the kernel by its instruction's name
    assert f"%{ssd.NAME}." in text


def test_causal_attention_compiles_for_v5e_at_published_widths(v5e_chip):
    """The attention path at 20 query / 4 key-value heads of 128 over
    4096 positions, heads side by side as the projections leave them."""
    from sparkdl_tpu.ops import attention

    rows, t, heads, kv, hd = 8, 4096, 20, 4, 128
    compiled = attention.attention_kernel.lower(
        _on_chip((rows, t, heads * hd), jnp.bfloat16, v5e_chip),
        _on_chip((rows, t, kv * hd), jnp.bfloat16, v5e_chip),
        _on_chip((rows, t, kv * hd), jnp.bfloat16, v5e_chip),
        heads=heads, kv_heads=kv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{attention.NAME}." in text
    # no row's [20, 4096, 4096] score matrix: nothing beside q, k, v, out
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_one_trunk_block_program_compiles_for_v5e(v5e_chip, monkeypatch):
    """One block of the trunk at the published widths through the
    engine's dispatch program (kernel paths on): it compiles, holds both
    kernels, and a dispatch of 8 rows x 4096 ids fits the chip beside
    its weights."""
    _compile_one_trunk_block(v5e_chip, monkeypatch, rows=8)


def test_one_trunk_block_program_compiles_for_v5e_at_the_tail_bucket(
        v5e_chip, monkeypatch):
    """The same at 4 rows: the bucket a job's tail of 4 rows dispatches
    in since the engine pads a piece to the smallest bucket of its plan
    (8 gives 2, 4, 8)."""
    _compile_one_trunk_block(v5e_chip, monkeypatch, rows=4)


def _compile_one_trunk_block(v5e_chip, monkeypatch, rows):
    import json
    import os

    from jax.sharding import Mesh

    from sparkdl_tpu.models import hybrid_trunk
    from sparkdl_tpu.ops import attention, ssd
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "falcon_h1_34b.json")) as fh:
        config = dict(json.load(fh), num_hidden_layers=1)
    monkeypatch.setattr(ssd, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    device = next(iter(v5e_chip.device_set))
    mesh = Mesh(np.asarray([device]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    variables = jax.eval_shape(lambda k: hybrid_trunk.init(config, k),
                               jax.random.PRNGKey(0))
    fn = hybrid_trunk.model_function(config, {}).fn
    compiled = build_dispatch_jit(fn, mesh, donate_batch=False).lower(
        variables, jax.ShapeDtypeStruct((rows, 4096), np.int32)).compile()
    text = compiled.as_text()
    assert f"%{ssd.NAME}." in text and f"%{attention.NAME}." in text
    mem = compiled.memory_analysis()
    # one block (0.86 GB) and the embedding (2.67 GB) beside the
    # temporaries of 32,768 tokens: five more blocks are 4.3 GB
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 10.5 * 2**30


# -- the expert trunk's kernels and program at the published widths ----------

def test_grouped_matmul_compiles_for_v5e_at_published_widths(v5e_chip):
    """The expert kernel at a chunk of ``trinity_large_preview.rows16k``:
    96 tiles of 256 rows, 3072 wide, the experts of four layers (128 x
    3072 x 6144 and 128 x 3072 x 3072) read in place."""
    from sparkdl_tpu.ops import grouped_matmul as gm

    bf16, i32 = jnp.bfloat16, jnp.int32
    slots, width, experts = 96 * gm.TILE, 3072, 4 * 32
    compiled = gm.grouped_matmul_kernel.lower(
        _on_chip((slots, width), bf16, v5e_chip),
        _on_chip((experts, width, 2 * width), bf16, v5e_chip),
        _on_chip((experts, width, width), bf16, v5e_chip),
        _on_chip((slots // gm.TILE,), i32, v5e_chip),
        _on_chip((), i32, v5e_chip), _on_chip((), i32, v5e_chip),
        out_dtype=jnp.float32).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{gm.NAME}." in text
    # the [rows, F] activations never reach memory
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_windowed_attention_compiles_for_v5e_at_published_widths(v5e_chip):
    """The attention kernel with the window as data: 6 query heads on 1
    key/value head of 128 over 16,384 positions."""
    from sparkdl_tpu.ops import attention

    rows, t, heads, kv, hd = 2, 16384, 6, 1, 128
    compiled = attention.attention_kernel.lower(
        _on_chip((rows, t, heads * hd), jnp.bfloat16, v5e_chip),
        _on_chip((rows, t, kv * hd), jnp.bfloat16, v5e_chip),
        _on_chip((rows, t, kv * hd), jnp.bfloat16, v5e_chip),
        _on_chip((), jnp.int32, v5e_chip), heads=heads, kv_heads=kv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{attention.NAME}." in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_the_expert_trunk_program_compiles_for_v5e(v5e_chip, monkeypatch):
    """``trinity_large_preview``'s whole program through the engine's
    dispatch program (kernel paths on) at the cell's dispatch of 2 rows x
    16,384 ids: it compiles, every kernel is ONE instruction whatever
    the depth, and the dispatch fits the chip beside its 9.0 GB of
    weights."""
    import json
    import os
    import re

    from jax.sharding import Mesh

    from sparkdl_tpu.models import expert_trunk
    from sparkdl_tpu.ops import attention, grouped_matmul
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity_large_preview.json")) as fh:
        config = json.load(fh)
    monkeypatch.setattr(grouped_matmul, "_on_tpu", lambda: True)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    device = next(iter(v5e_chip.device_set))
    mesh = Mesh(np.asarray([device]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    variables = jax.eval_shape(lambda k: expert_trunk.init(config, k),
                               jax.random.PRNGKey(0))
    fn = expert_trunk.model_function(config, {}).fn
    compiled = build_dispatch_jit(fn, mesh, donate_batch=False).lower(
        variables, jax.ShapeDtypeStruct((2, 16384), np.int32)).compile()
    text = compiled.as_text()
    kernels = sorted(set(re.findall(
        r"%(?:causal_attention|grouped_matmul)\.\d+ = ", text)))
    assert len(kernels) == 2, kernels
    # 512 rows an expert: the tile the rule gives is the ceiling's, the
    # chunk 96 tiles of 256 — the instruction this cell has run since PR 35
    assert re.search(r"%grouped_matmul\.\d+ = f32\[24576,3072\]", text)
    mem = compiled.memory_analysis()
    # the issue's arithmetic: 0.242 + 4 x 1.886 + 1.230 GB of weights
    assert 9.0e9 < mem.argument_size_in_bytes < 9.03e9
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 13.5e9


# -- block diffusion: the kernels as the generator calls them, and its program

def test_block_mask_attention_compiles_for_v5e_at_published_widths(v5e_chip):
    """The attention kernel under the mask by blocks of 4 at a group of
    ``sdar_30b_a3b_chat.gen256``'s prefill: 8 prompts of 1024 ids, 32
    query heads on 4 key/value heads of 128."""
    from sparkdl_tpu.ops import attention

    rows, t, heads, kv, hd = 8, 1024, 32, 4, 128
    compiled = attention.attention_kernel.lower(
        _on_chip((rows, t, heads * hd), jnp.bfloat16, v5e_chip),
        _on_chip((rows, t, kv * hd), jnp.bfloat16, v5e_chip),
        _on_chip((rows, t, kv * hd), jnp.bfloat16, v5e_chip),
        heads=heads, kv_heads=kv, block_length=4).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{attention.NAME}." in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_grouped_matmul_compiles_for_v5e_at_a_pass_of_the_loop(v5e_chip):
    """The expert kernel as a pass of the generation loop calls it: a
    chunk of 224 tiles of 16 rows for 2,048 pairs (16 rows an expert),
    2048 wide, the 6 x 128 experts of width 768 read in place, a block
    ``F`` whole."""
    from sparkdl_tpu.ops import grouped_matmul as gm

    bf16, i32 = jnp.bfloat16, jnp.int32
    tile = gm.tile_for(2048 / 128)
    slots, width, f, experts = 224 * tile, 2048, 768, 6 * 128
    assert tile == 16 and gm.block_f_for(width, f, width, 2) == f
    compiled = gm.grouped_matmul_kernel.lower(
        _on_chip((slots, width), bf16, v5e_chip),
        _on_chip((experts, width, 2 * f), bf16, v5e_chip),
        _on_chip((experts, f, width), bf16, v5e_chip),
        _on_chip((slots // tile,), i32, v5e_chip),
        _on_chip((), i32, v5e_chip), _on_chip((), i32, v5e_chip),
        tile=tile, out_dtype=jnp.float32).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{gm.NAME}." in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_cache_attention_compiles_for_v5e_at_a_pass_of_the_loop(v5e_chip):
    """The loop's attention as a pass of ``sdar_30b_a3b_chat.gen256``
    calls it: 64 rows of 4 queries, 32 query heads on 4 key/value heads
    of 128, the carried cache of all 6 layers ``[6, 64, 1280, 512]``
    handed over whole, the layer and the filled length as data."""
    import re

    from sparkdl_tpu.ops import cache_attention as ca

    rows, b, heads, kv, hd, depth, t = 64, 4, 32, 4, 128, 6, 1280
    bf16, i32 = jnp.bfloat16, jnp.int32
    tile = ca.key_tile(b, b, t, True)
    assert tile == ca.TILE
    compiled = ca.cache_attention_kernel.lower(
        _on_chip((rows, b, heads * hd), bf16, v5e_chip),
        _on_chip((rows, b, kv * hd), bf16, v5e_chip),
        _on_chip((rows, b, kv * hd), bf16, v5e_chip),
        _on_chip((depth, rows, t, kv * hd), bf16, v5e_chip),
        _on_chip((depth, rows, t, kv * hd), bf16, v5e_chip),
        _on_chip((), i32, v5e_chip), _on_chip((), i32, v5e_chip),
        heads=heads, kv_heads=kv, tile=tile, rows=ca.ROWS).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and f"%{ca.NAME}." in text
    # no layer's cache beside the cache: nothing but the queries' layout
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20
    assert not re.search(r"bf16\[(1,)?64,1280,512\]", text)


def test_the_block_diffusion_program_compiles_for_v5e(v5e_chip, monkeypatch):
    """``sdar_30b_a3b_chat``'s whole program — prefill, the loop with its
    cache, the head and the sampler — through the engine's dispatch
    program (kernel paths on) at the cell's dispatch of 64 prompts of
    1,024 ids: it compiles; the prefill's attention is ONE instruction,
    the loop's attention ONE (it is handed the carried cache whole: no
    layer's cache is sliced or copied) and the expert kernel two (the
    prefill's and the loop's), whatever the depth and the number of
    passes; the generation loop is the one ``while`` whose carry leads
    with the generated ids (the device trace's line that
    ``diffusion_flops.loop_seconds`` reads); and the dispatch fits the
    chip beside its 8.72 GB of weights."""
    import json
    import os
    import re

    from jax.sharding import Mesh

    from benchmark import trace_reduce
    from sparkdl_tpu.models import block_diffusion
    from sparkdl_tpu.ops import attention, cache_attention, grouped_matmul
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "sdar_30b_a3b_chat.json")) as fh:
        config = json.load(fh)
    for module in (grouped_matmul, attention, cache_attention):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    device = next(iter(v5e_chip.device_set))
    mesh = Mesh(np.asarray([device]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    variables = jax.eval_shape(lambda k: block_diffusion.init(config, k),
                               jax.random.PRNGKey(0))
    fn = block_diffusion.model_function(
        config, {}, generated_length=config["generated_length"],
        denoise_steps=config["denoise_steps"]).fn
    compiled = build_dispatch_jit(fn, mesh, donate_batch=False).lower(
        variables, {"ids": jax.ShapeDtypeStruct(
            (64, config["prompt_length"]), np.int32)}).compile()
    text = compiled.as_text()
    kernels = sorted(name.split(".")[0] for name in set(re.findall(
        r"%((?:causal_attention|cache_attention|grouped_matmul)\.\d+) = ",
        text)))
    assert kernels == ["cache_attention", "causal_attention",
                       "grouped_matmul", "grouped_matmul"], kernels
    # the carried cache goes to the loop's attention as it stands: no
    # instruction holds one layer's cache
    assert not re.search(r"bf16\[(1,)?64,1280,512\]", text)
    # the prefill's chunk of 384 tiles of 256 as before; the loop's, the
    # one with the fewer rows, 224 tiles of 16
    assert sorted(int(n) for n in re.findall(
        r"%grouped_matmul\.\d+ = f32\[(\d+),2048\]", text)) == [
            3584, 98304]
    loops = [trace_reduce.short_op_name(line.strip())
             for line in text.splitlines()
             if re.match(r"\s*%while[\w.]* = ", line)]
    assert [name.split(" ", 1)[1] for name in loops
            if "s32[64,256]" in name] == ["s32[64,256] while"], loops
    mem = compiled.memory_analysis()
    # the issue's arithmetic: 6 x 1.246 + 1.244 GB of weights
    assert 8.72e9 < mem.argument_size_in_bytes < 8.73e9
    # the cache (1.007 GB) and the prefill's and a pass's temporaries
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 13.0e9


# -- the latent trunk: learned sparse attention and its whole program ----------

def test_sparse_attention_kernels_compile_for_v5e_at_published_widths(
        v5e_chip):
    """The three kernels at a chunk of ``deepseek_v3_2_exp.rows32k``:
    4096 queries of a row of 32,768 positions, the indexer's 64 heads of
    128, the top 2,048, 128 heads of 128 + 64 over keys read in place.
    The attention at the block, group and sub-tile it takes (256
    queries, 16 heads unrolled into 32 pipelined pieces of 256 keys, the
    key axis as data) compiles within ``VMEM_LIMIT``: Mosaic refuses a
    kernel whose blocks and scratch do not fit the limit it is given."""
    import re

    from sparkdl_tpu.ops import sparse_attention as sa

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    q, t = 4096, 32768
    at = _on_chip((), i32, v5e_chip)
    scores = _on_chip((q, t), f32, v5e_chip)
    sel = _on_chip((q, 2), i32, v5e_chip)
    for name, compiled in (
            (sa.INDEXER_NAME, sa.indexer_scores_kernel.lower(
                _on_chip((q, 64, 128), bf16, v5e_chip),
                _on_chip((t, 128), bf16, v5e_chip),
                _on_chip((q, 64), f32, v5e_chip), at).compile()),
            (sa.SELECT_NAME, sa.topk_select_kernel.lower(
                scores, at, top=2048).compile()),
            (sa.ATTENTION_NAME, sa.sparse_attention_kernel.lower(
                _on_chip((128, q, 128), bf16, v5e_chip),
                _on_chip((128, q, 64), bf16, v5e_chip),
                _on_chip((128, t, 128), bf16, v5e_chip),
                _on_chip((128, t, 128), bf16, v5e_chip),
                _on_chip((t, 64), bf16, v5e_chip), scores, sel, at
            ).compile())):
        text = compiled.as_text()
        assert "tpu_custom_call" in text and f"%{name}." in text, name
        kernel = [line for line in text.splitlines()
                  if f"%{name}." in line and "custom-call(" in line]
        assert re.findall(r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                          "".join(kernel)) == [str(sa.VMEM_LIMIT)], name
        # the selection reads the scores in place; the others at most
        # lay out their queries anew (the 64-wide q_pe: 67 MB)
        limit = 2**20 if name == sa.SELECT_NAME else 2**28
        assert compiled.memory_analysis().temp_size_in_bytes < limit, name


def test_the_latent_trunk_program_compiles_for_v5e(v5e_chip, monkeypatch):
    """``deepseek_v3_2_exp``'s whole program through the engine's dispatch
    program (kernel paths on) at the cell's dispatch of 1 row x 32,768
    ids: it compiles, every kernel is ONE instruction whatever the depth
    and the chunks, and the dispatch fits the chip beside its 9.04 GB of
    weights.  The expert kernel's blocks of ``F`` narrow to 256 at these
    widths (``grouped_matmul._step_bytes``)."""
    import json
    import re

    from jax.sharding import Mesh

    from sparkdl_tpu.models import latent_trunk
    from sparkdl_tpu.ops import grouped_matmul, sparse_attention
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deepseek_v3_2_exp.json")) as fh:
        config = json.load(fh)
    for module in (grouped_matmul, sparse_attention):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    device = next(iter(v5e_chip.device_set))
    mesh = Mesh(np.asarray([device]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    variables = jax.eval_shape(lambda k: latent_trunk.init(config, k),
                               jax.random.PRNGKey(0))
    fn = latent_trunk.model_function(config, {}).fn
    compiled = build_dispatch_jit(fn, mesh, donate_batch=False).lower(
        variables, jax.ShapeDtypeStruct((1, 32768), np.int32)).compile()
    text = compiled.as_text()
    kernels = sorted(name.split(".")[0] for name in set(re.findall(
        r"%((?:indexer_scores|topk_select|sparse_attention|grouped_matmul)"
        r"\.\d+) = ", text)))
    assert kernels == ["grouped_matmul", "indexer_scores",
                       "sparse_attention", "topk_select"], kernels
    mem = compiled.memory_analysis()
    # the issue's arithmetic: 1.195 + 4 x 1.903 + 0.232 GB of weights
    assert 9.03e9 < mem.argument_size_in_bytes < 9.05e9
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 15.5e9


def test_the_latent_trunk_weights_are_drawn_in_place_on_v5e(v5e_chip):
    """``deepseek_v3_2_exp``'s stacks, drawn by the reference's rule in
    the ONE program ``stack_layers`` makes: 8.81 GB out, and beside it no
    more than a layer's pieces (an expert layer's 1.8 GB and their
    float32 draw), never a second copy of a stack."""
    import json

    from benchmark.reference import deepseek_v32
    from sparkdl_tpu.models import latent_trunk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "deepseek_v3_2_exp.json")) as fh:
        config = json.load(fh)
    weights = deepseek_v32.draw_weights(config, 3_000_000_011)
    compiled = jax.jit(lambda: latent_trunk.stack_layers(weights.leaf, config),
                       out_shardings=v5e_chip).lower().compile()
    mem = compiled.memory_analysis()
    assert 8.80e9 < mem.output_size_in_bytes < 8.82e9
    assert mem.temp_size_in_bytes < 3e9


# -- the vision-language trunk: frame attention, grouped sparse attention ------

def test_vl_trunk_kernels_compile_for_v5e_at_published_widths(v5e_chip):
    """``keye_vl_2_30b_a3b.video32k``'s kernels at their shapes: the
    tower's attention over a chunk of 8 frames of 1024 patches, 16 heads
    of 72 first (72 the whole last axis), turned by the 2-D rotary
    position inside the kernel (no array of half a head); the
    indexer at 16 heads of 64 against one key head, and the attention of
    32 query heads over 4 key/value heads of 128 with no rotary part, at
    a chunk of 4096 queries of a row of 32,768."""
    import re

    from sparkdl_tpu.ops import attention
    from sparkdl_tpu.ops import sparse_attention as sa

    bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    frames = _on_chip((8, 16, 1024, 72), bf16, v5e_chip)
    q, t = 4096, 32768
    at = _on_chip((), i32, v5e_chip)
    scores = _on_chip((q, t), f32, v5e_chip)
    sel = _on_chip((q, 2), i32, v5e_chip)
    for name, compiled in (
            (attention.FRAME_NAME, attention.frame_attention_kernel.lower(
                frames, frames, frames,
                _on_chip((1024, 36), f32, v5e_chip)).compile()),
            (sa.INDEXER_NAME, sa.indexer_scores_kernel.lower(
                _on_chip((q, 16, 64), bf16, v5e_chip),
                _on_chip((t, 64), bf16, v5e_chip),
                _on_chip((q, 16), f32, v5e_chip), at).compile()),
            (sa.ATTENTION_NAME, sa.sparse_attention_kernel.lower(
                _on_chip((32, q, 128), bf16, v5e_chip), None,
                _on_chip((4, t, 128), bf16, v5e_chip),
                _on_chip((4, t, 128), bf16, v5e_chip), None, scores, sel,
                at).compile())):
        text = compiled.as_text()
        assert "tpu_custom_call" in text and f"%{name}." in text, name
    assert not re.search(r"bf16\[8,16,1024,128\]", text)


def test_the_vl_trunk_program_compiles_for_v5e(v5e_chip, monkeypatch):
    """``keye_vl_2_30b_a3b``'s whole program through the engine's dispatch
    program (kernel paths on) at the cell's dispatch of 1 row: 120 frames
    of 448 x 448 x 3 uint8 beside 32,768 ids.  It compiles, every kernel
    is ONE instruction whatever the depth, the chunks of frames and of
    queries, and the dispatch fits the chip beside its 9.01 GB of
    weights."""
    import json
    import re

    from jax.sharding import Mesh

    from sparkdl_tpu.models import vl_trunk
    from sparkdl_tpu.ops import attention, grouped_matmul, sparse_attention
    from sparkdl_tpu.parallel import mesh as mesh_lib
    from sparkdl_tpu.parallel.engine import build_dispatch_jit

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye_vl_2_30b_a3b.json")) as fh:
        config = json.load(fh)
    for module in (attention, grouped_matmul, sparse_attention):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    device = next(iter(v5e_chip.device_set))
    mesh = Mesh(np.asarray([device]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    variables = jax.eval_shape(lambda k: vl_trunk.init(config, k),
                               jax.random.PRNGKey(0))
    fn = vl_trunk.model_function(config, {}).fn
    compiled = build_dispatch_jit(fn, mesh, donate_batch=False).lower(
        variables, {"pixels": jax.ShapeDtypeStruct((1, 120 * 448 * 448 * 3),
                                                   np.uint8),
                    "ids": jax.ShapeDtypeStruct((1, 32768), np.int32)}
    ).compile()
    text = compiled.as_text()
    kernels = sorted(name.split(".")[0] for name in set(re.findall(
        r"%((?:frame_attention|indexer_scores|topk_select|sparse_attention"
        r"|grouped_matmul)\.\d+) = ", text)))
    assert kernels == ["frame_attention", "grouped_matmul", "indexer_scores",
                       "sparse_attention", "topk_select"], kernels
    mem = compiled.memory_analysis()
    print("arguments", mem.argument_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    # the configuration's arithmetic: 0.885 + 0.622 + 6 x 1.251 = 9.013 GB of
    # weights as drawn, laid out in the chip's tiles, and the dispatch's
    # 72 MB of pixels
    assert 9.2e9 < mem.argument_size_in_bytes < 9.35e9
    assert (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes) < 15.0e9


def test_the_vl_trunk_weights_are_drawn_in_place_on_v5e(v5e_chip):
    """``keye_vl_2_30b_a3b``'s weights, drawn by the reference's rule in
    ONE program that takes the seed as its argument (one compiled draw
    for every seed): 9.01 GB out, and beside it no more than a layer's
    pieces and their float32 draw, never a second copy of a stack."""
    import json

    from benchmark.reference import keye_vl2
    from sparkdl_tpu.models import vision_tower, vl_trunk

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "keye_vl_2_30b_a3b.json")) as fh:
        config = json.load(fh)

    def draw(parts):
        w = keye_vl2.draw_weights(config, parts)
        return {"vision": vision_tower.stack_tower(
                    w.vision_leaf, config["vision_config"],
                    config["hidden_size"]),
                "embed_tokens": w.embedding(),
                **vl_trunk.stack_layers(w.leaf, config),
                "norm": w.final_norm()}

    compiled = jax.jit(draw, out_shardings=v5e_chip).lower(
        _on_chip((2,), jnp.int32, v5e_chip)).compile()
    mem = compiled.memory_analysis()
    print("draw out", mem.output_size_in_bytes, "temporaries",
          mem.temp_size_in_bytes)
    assert 9.0e9 < mem.output_size_in_bytes < 9.3e9
    assert mem.temp_size_in_bytes < 3e9
