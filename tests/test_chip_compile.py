"""The kernels of the main path, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a device that is
described, not attached (``jax.experimental.topologies``): what it
refuses — a block that is not a legal tile, a slice off the tiling, a
tile too large for VMEM — it refuses here, at gpt3_1p3b and gpt2-medium
widths, in a second or two per kernel, where interpret mode accepts
anything.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU's library at a time, every
xdist worker imports this file, and only the worker that is given it
may make the call. All of these tests live in this one file for the
same reason. Kernel selection asks ``framework.place.on_tpu``, which
sees this process's CPU; the ``compiled_kernels`` fixture steers it
from here.
"""
import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.paged_attention import kv_pool_shape

# gpt3_1p3b attention geometry and the serving engine's defaults
H, D, B, PAGE, SLOTS = 16, 128, 8, 16, 2048
# the benchmark's two serving shapes: (head_dim, lanes, table slots)
SERVE_SHAPES = {"1p3b": (128, 16, 2048), "medium": (64, 32, 1024)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the TPU
        # compiler from describing a chip here (no libtpu, its lock held
        # by another process) means these tests cannot run, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def jax_config_as_on_the_chip():
    """A compile for a described chip is written to jax's persistent
    cache but cannot be read back without the chip; keep it off. And
    conftest's "highest" matmul precision is the CPU oracle's: the
    program never sets it, and Mosaic refuses an fp32-precision
    contraction of bf16 operands ("Bad lhs type")."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch, tmp_path):
    """Kernel selection as on the chip: compiled, never interpreted,
    with an empty autotune table."""
    from paddle_tpu.framework import place
    from paddle_tpu.ops import autotune
    monkeypatch.setattr(place, "on_tpu", lambda: True)
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    monkeypatch.setattr(autotune, "_loaded", False)
    monkeypatch.setattr(autotune, "_cache", {})


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "compiled, but no Pallas kernel is in the program"
    return compiled


# ------------------------------------------------------ flash attention
@pytest.mark.parametrize("blocks", [(512, 1024), (128, 128)])
@pytest.mark.parametrize("backward", [False, True],
                         ids=["fwd", "fwd+bwd"])
def test_mha_compiles(one_chip, compiled_kernels, blocks, backward):
    """``pallas_attention.mha`` at the 1.3B train step's attention
    shape, default blocks and the smallest legal ones."""
    from paddle_tpu.ops.pallas_attention import mha
    x = jax.ShapeDtypeStruct((2, H, 2048, D), jnp.bfloat16,
                             sharding=one_chip)

    def fwd(q, k, v):
        return mha(q, k, v, True, D ** -0.5, *blocks)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fwd(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    _compile(fwd_bwd if backward else fwd, x, x, x)


# ------------------------------------------------------ paged attention
def _paged_args(one_chip, kind, q_dtype, pool, seq, shape=(D, B, SLOTS)):
    d, b, slots = shape
    pages = slots // PAGE
    num_pages = 1 + b * pages

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if pool == "int8":
        kv = (sds(kv_pool_shape(num_pages, PAGE, H, d), jnp.int8),
              sds(kv_pool_shape(num_pages, PAGE, H), jnp.float32))
    else:
        kv = sds(kv_pool_shape(num_pages, PAGE, H, d), pool)
    s = 1 if kind == "decode" else seq
    return (sds((b, s, H, d), q_dtype), kv, kv,
            sds((b, pages), jnp.int32), sds((b,), jnp.int32),
            sds((b, s), jnp.int32), sds((b, s), jnp.int32))


POOLS = [(jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
         (jnp.float32, "int8"), (jnp.bfloat16, "int8")]


@pytest.mark.parametrize(
    "q_dtype,pool", POOLS,
    ids=["f32", "bf16", "f32-q-int8-pool", "bf16-q-int8-pool"])
@pytest.mark.parametrize("kind,seq", [("decode", 1), ("chunked", 64),
                                      ("chunked", 5)])
def test_paged_attention_compiles(one_chip, compiled_kernels, kind, seq,
                                  q_dtype, pool):
    """The fused read-through-table kernels at gpt3_1p3b widths (16
    heads of 128, batch 8, 2048-slot tables of 16-slot pages): decode
    (the page-copying kernel over f32 and bf16 pools, the grid kernel
    over int8 ones), a suffix-prefill window, and a speculative-verify
    window no 8-multiple divides — over f32, bf16 and int8 pools, with
    the blocks ``ops/autotune.py`` picks unaided."""
    from paddle_tpu.ops.pallas_paged_attention import (paged_attention,
                                                       supported)
    args = _paged_args(one_chip, kind, q_dtype, pool, seq)
    assert supported(args[0], args[1], args[3], PAGE, kind)
    _compile(functools.partial(paged_attention, page_size=PAGE, kind=kind,
                               scale=D ** -0.5), *args)


@pytest.mark.parametrize("shape", sorted(SERVE_SHAPES))
@pytest.mark.parametrize("kind,seq", [("decode", 1), ("chunked", 64),
                                      ("chunked", 4)])
def test_paged_attention_compiles_at_the_serving_shapes(
        one_chip, compiled_kernels, kind, seq, shape):
    """Decode, a suffix-prefill window and the speculative-verify
    window over f32 pools at what the two serve cells run: 16 heads of
    128 over 128-page tables at 16 lanes, 16 heads of 64 over 64-page
    tables at 32 lanes (decode: the page-copying kernel for both, a
    page a dense [16, 2048] or [16, 1024] tile), with the block
    constants ``ops/autotune.py`` holds."""
    from paddle_tpu.ops.pallas_paged_attention import (
        decode_copies_pages, paged_attention)
    d, lanes, slots = SERVE_SHAPES[shape]
    assert decode_copies_pages(H * d, False)
    args = _paged_args(one_chip, kind, jnp.float32, jnp.float32, seq,
                       shape=(d, lanes, slots))
    _compile(functools.partial(paged_attention, page_size=PAGE, kind=kind,
                               scale=d ** -0.5), *args)


@pytest.mark.parametrize("chunk", [1, 2, 8, 16])
def test_decode_pages_per_chunk_ladder_compiles(one_chip,
                                                compiled_kernels, chunk):
    """The page-copying kernel at the chunk sizes timed on the chip
    beside the constant (16 pages: 8 MiB of page buffers, the most
    ``paged_decode_chunk`` lets the constant take)."""
    from paddle_tpu.ops import autotune
    from paddle_tpu.ops.pallas_paged_attention import paged_attention
    assert autotune.paged_decode_chunk(PAGE, H * D, 4, 128) == \
        autotune.PAGED_DECODE_PAGES_PER_CHUNK
    assert autotune.paged_decode_chunk(PAGE, H * D, 4, 2) == 2
    assert autotune.paged_decode_chunk(PAGE, H * 8 * D, 4, 128) == 2
    args = _paged_args(one_chip, "decode", jnp.float32, jnp.float32, 1,
                       shape=SERVE_SHAPES["1p3b"])
    _compile(functools.partial(paged_attention, page_size=PAGE,
                               kind="decode", scale=D ** -0.5,
                               pages_per_chunk=chunk), *args)


@pytest.mark.parametrize("heads", [4, 8])
def test_decode_compiles_on_a_heads_shard(one_chip, compiled_kernels,
                                          heads):
    """Under a live ``mp`` mesh each rank runs the kernel on its
    heads-block of q and the pools (``_sharded_paged_attention``): the
    page-copying kernel at 16 / 4 and 16 / 2 heads of 128."""
    from paddle_tpu.ops.pallas_paged_attention import paged_attention

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds(kv_pool_shape(1201, PAGE, heads, D), jnp.float32)
    _compile(functools.partial(paged_attention, page_size=PAGE,
                               kind="decode", scale=D ** -0.5),
             sds((16, 1, heads, D), jnp.float32), pool, pool,
             sds((16, 128), jnp.int32), sds((16,), jnp.int32),
             sds((16, 1), jnp.int32), sds((16, 1), jnp.int32))


@pytest.mark.parametrize("config", ["gpt3_1p3b", "gpt2_medium"])
def test_default_decode_program_reads_through_the_table(
        one_chip, compiled_kernels, config):
    """The decode program a ``CachedDecoder`` built with defaults lowers
    for the chip (two layers at full width, the cell's lanes and table)
    holds the Mosaic call, and no array of shape ``[B, P*page_size, H,
    D]``: the gathered context is gone from the program, not merely
    unused."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    d, lanes, slots = SERVE_SHAPES[
        "1p3b" if config == "gpt3_1p3b" else "medium"]
    pages = slots // PAGE
    paddle.seed(0)
    model = models.GPTForCausalLM(getattr(models, config)(
        num_layers=2, vocab_size=1024))
    model.eval()
    dec = CachedDecoder(model, max_batch=lanes, page_size=PAGE,
                        pages_per_seq=pages, donate=True,
                        max_positions=slots, kv_dtype="")
    assert dec.use_pallas is True           # nobody asked: the backend

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    params, buffers = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), state_arrays(model))
    pool = [sds(kv_pool_shape(1 + lanes * 8, PAGE, H, d), jnp.float32)] * 2
    text = dec._decode_jit.lower(
        params, buffers, sds((lanes,), jnp.int32), sds((lanes,), jnp.int32),
        sds((lanes,), jnp.bool_), sds((lanes,), jnp.int32),
        sds((lanes, pages), jnp.int32), sds((lanes,), jnp.float32),
        sds((lanes,), jnp.float32), pool, pool).compile().as_text()
    assert text.count("tpu_custom_call") == 2       # one a layer
    assert "paged_attention/attend" in text         # and it carries the scope
    assert f"[{lanes},{slots},{H},{d}]" not in text
    assert f"[{lanes * slots},{H},{d}]" not in text


# the three serving configurations as their cells run them, two layers
# deep (a whole-context and a window layer where there are both kinds),
# nothing cut that a pool's shape or a program's use of it depends on:
# preset, cuts, lanes, table slots, pool pages (full, window), a prefill
POOL_PROGRAMS = {
    "gpt2_medium": ({"vocab_size": 1024}, 32, 1024, (2100, None), (1, 128)),
    "gpt3_1p3b": ({"vocab_size": 1024}, 16, 2048, (1200, None), (1, 256)),
    "smallthinker_21ba3b": (
        {"vocab_size": 1024, "moe_num_experts": 8, "dtype": "bfloat16",
         "max_seq_len": 9216}, 32, 9216, (18433, 8225), (1, 1024)),
}


def _lower_serve_program(one_chip, config, program):
    """``(model, lanes, lowered)``: ``program`` (``"decode"`` or a
    prefill's ``(rows, seq)``) of a ``CachedDecoder`` built with
    defaults over ``POOL_PROGRAMS[config]``'s model, lanes, table and
    donated pools, lowered for the described chip."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.ops.paged_attention import ring_pages
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    cuts, lanes, slots, (pages, window_pages), _ = POOL_PROGRAMS[config]
    paddle.seed(0)
    model = models.GPTForCausalLM(getattr(models, config)(
        num_layers=2, **cuts))
    model.eval()
    width = slots // PAGE
    if window_pages:
        width += ring_pages(model.config.sliding_window, PAGE)
    dec = CachedDecoder(model, max_batch=lanes, page_size=PAGE,
                        pages_per_seq=width, donate=True,
                        max_positions=slots, kv_dtype="")
    assert dec.use_pallas is True

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params, buffers = described(state_arrays(model))
    k, v = described(jax.eval_shape(lambda: model.init_kv_pools(
        pages, PAGE, window_pages=window_pages)))
    if program == "decode":
        lowered = dec._decode_jit.lower(
            params, buffers, sds((lanes,), jnp.int32),
            sds((lanes,), jnp.int32), sds((lanes,), jnp.bool_),
            sds((lanes,), jnp.int32), sds((lanes, width), jnp.int32),
            sds((lanes,), jnp.float32), sds((lanes,), jnp.float32), k, v)
    else:
        rows, seq = program
        lowered = dec._prefill_jit.lower(
            params, buffers, sds((rows, seq), jnp.int64),
            sds((rows,), jnp.int32), sds((rows, width), jnp.int32),
            sds((rows,), jnp.float32), sds((rows,), jnp.float32), k, v)
    return model, lanes, lowered


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("config", sorted(POOL_PROGRAMS))
def test_no_program_copies_a_pool_at_its_boundary(
        one_chip, compiled_kernels, config, program):
    """The decode program and a prefill program of a ``CachedDecoder``
    built with defaults, compiled for the chip over donated pools of
    the cells' sizes (gpt2-medium f32, 1024 lanes a row; gpt3-1p3b f32,
    2048; SmallThinker bf16, 512, a whole-context and a window pool):
    every pool-shaped array in the program is row-major, the arguments
    among them, and no ``copy`` makes one. With the heads an axis of
    the pool the compiler made the page axis minor for 64-wide heads
    and each layer's scatter and kernel paid four transposing copies of
    a whole pool: 96 a gpt2-medium decode step (PERF.md, PR 30)."""
    pages, window_pages = POOL_PROGRAMS[config][3]
    model, _, lowered = _lower_serve_program(
        one_chip, config,
        program if program == "decode" else POOL_PROGRAMS[config][4])
    text = lowered.compile().as_text()
    spec = model.kv_cache_spec()
    for n in {pages, window_pages or pages}:
        shape = ",".join(map(str, kv_pool_shape(
            n, PAGE, spec["num_kv_heads"], spec["head_dim"])))
        pool = r"(?:f32|bf16)\[" + shape + r"\]"
        layouts = set(re.findall(pool + r"\{([\d,]+)", text))
        assert layouts == {"2,1,0"}, layouts
        assert not re.findall(pool + r"\S* copy\(", text)


# the single-row prefill of every bucket a GPT cell warms, a two-row
# prefill and the decode step: where the compiler's choice of what to
# fuse into the MLP's down-projection turns on the activation's size
MLP_PROGRAMS = [
    (config, program)
    for config, buckets in (("gpt2_medium", (128, 256, 512, 768)),
                            ("gpt3_1p3b", (128, 256, 512, 1024)))
    for program in ("decode", *((1, seq) for seq in buckets), (2, 128))]


def _computations(text):
    """``{name: instruction lines}`` of a compiled module's HLO text,
    and the entry computation's name."""
    comps, entry, name = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            if head.group(1):
                entry = name
        elif line.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps, entry


@pytest.mark.parametrize(
    "config,program", MLP_PROGRAMS,
    ids=[f"{c}-{p if p == 'decode' else 'prefill[%d,%d]' % p}"
         for c, p in MLP_PROGRAMS])
def test_no_matmul_is_computed_inside_another_matmuls_fusion(
        one_chip, compiled_kernels, config, program):
    """In no prefill program a GPT serve cell warms (two layers at full
    width, the cells' lanes, tables and pools, compiled for the chip)
    does a computation that holds a ``convolution`` call a fusion whose
    computation holds one: a product that is a *producer* inside
    another product's fusion is computed again for every output tile of
    it. Without the barrier in ``GPTMLP.forward`` (the parent of PR
    32, where these ten cases fail) the compiler nests ``fc_in``'s
    product and GELU inside ``fc_out``'s fusion in six of them
    (gpt3_1p3b ``prefill[1,128]``, ``[1,256]``; gpt2_medium
    ``[1,128]``, ``[1,256]``, ``[1,512]``, ``[2,128]``), and 1.3B's
    ``[1,256]`` took 108 ms for it where ``[1,512]`` takes 16 (PERF.md,
    PR 32). With it the up-projection is an array of the entry
    computation, written once by one fusion a layer. The decode step,
    one position a row, is left to the compiler (the barrier costs it a
    fusion boundary a layer: 2.4% of gpt2-medium's step): it lowers
    without a barrier, and what the compiler nests there is a product
    of ``lanes`` rows, a single tile."""
    model, lanes, lowered = _lower_serve_program(one_chip, config, program)
    barriers = lowered.as_text().count("optimization_barrier")
    comps, entry = _computations(lowered.compile().as_text())
    calls = {name: re.findall(r" fusion\(.*calls=%?([\w.\-]+)",
                              "\n".join(lines))
             for name, lines in comps.items()}
    # rows of each product a computation holds: its result's dimensions
    # but the last, multiplied
    products = {name: [math.prod(map(int, dims.split(",")[:-1]))
                       for dims in re.findall(
                           r"= \w+\[([\d,]+)\]\S* convolution\(",
                           "\n".join(lines))]
                for name, lines in comps.items()}
    assert sum(map(bool, products.values())) >= 8   # qkv, out, fc_in, fc_out

    def rows_inside(name):
        return products[name] + [
            r for inner in calls[name] for r in rows_inside(inner)]

    nested = [rows for outer in sorted(comps) if products[outer]
              for inner in calls[outer] if (rows := rows_inside(inner))]
    if program == "decode":
        assert barriers == 0
        assert all(rows == [lanes] for rows in nested), nested
        return
    assert not nested, nested
    assert barriers == 2                        # one a layer
    if (config, program) == ("gpt3_1p3b", (1, 256)):
        inter = model.config.intermediate_size
        written = [line for line in comps[entry] if re.search(
            rf"= f32\[1,256,{inter}\]\S* fusion\(", line)]
        assert len(written) == 2, written       # one a layer


def test_every_paged_block_candidate_compiles(one_chip, compiled_kernels):
    """The autotuner's table holds legal tiles only: each candidate it
    would time on the chip compiles (the old table's head blocks of 1,
    2 and 4 were all refused at 16 heads)."""
    from paddle_tpu.ops import autotune
    from paddle_tpu.ops.pallas_paged_attention import paged_attention
    for quantized, (q_dtype, pool) in ((False, POOLS[1]),
                                       (True, POOLS[3])):
        args = _paged_args(one_chip, "decode", q_dtype, pool, 1)
        cands = autotune.paged_block_candidates(
            "decode", 1, H, D, PAGE, SLOTS // PAGE, quantized=quantized)
        assert len(cands) >= 3
        for bq, bh, ppt in cands:
            _compile(functools.partial(
                paged_attention, page_size=PAGE, kind="decode",
                scale=D ** -0.5, block_q=bq, block_h=bh,
                pages_per_tile=ppt), *args)


def test_illegal_head_block_is_refused_by_name(one_chip, compiled_kernels):
    """A tile the lowering does not take raises the compiler's own
    message on the chip — there is no interpret mode to fall back to.
    (A window of queries: its q block is ``[block_q, block_h, D]``. For
    float decode one head a block is legal since the heads lie in the
    pool's lanes: a head of 128 is a whole lane tile.)"""
    from paddle_tpu.ops.pallas_paged_attention import paged_attention
    args = _paged_args(one_chip, "chunked", *POOLS[1], 64)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.jit(functools.partial(
            paged_attention, page_size=PAGE, kind="chunked",
            scale=D ** -0.5, block_h=1)).lower(*args)
    _compile(functools.partial(
        paged_attention, page_size=PAGE, kind="decode", scale=D ** -0.5,
        block_h=1), *_paged_args(one_chip, "decode", *POOLS[1], 1))


# ------------- grouped K/V heads, sliding windows, experts (PR 29) ------
# the expert configuration's serving geometry: 28 query heads over 4 K/V
# heads of 128, bf16 pools, 32 lanes; a full layer's 576-page tables over
# 18,433 pages, a window layer's ring of 257 over 1 + 32 x 257
GQA_HEADS, GQA_KV_HEADS, GQA_LANES, WINDOW = 28, 4, 32, 4096
GQA_KINDS = {"full": (None, 576, 18433), "window": (WINDOW, 257, 8225)}


@pytest.mark.parametrize("kind", sorted(GQA_KINDS))
def test_grouped_decode_compiles_for_both_kinds_of_layer(
        one_chip, compiled_kernels, kind):
    """``paged_attention_update`` as the expert configuration's decode
    step calls it: the page-copying kernel with a group of 7 query
    heads a K/V head on the MXU, over the whole context and over a
    ring of the last 4096 positions; the bf16 pool reaches the kernel
    as it lies (no copy of it in the program)."""
    from paddle_tpu.ops.paged_attention import paged_attention_update
    window, width, pages = GQA_KINDS[kind]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds(kv_pool_shape(pages, PAGE, GQA_KV_HEADS, D), jnp.bfloat16)
    kv = sds((GQA_LANES, 1, GQA_KV_HEADS, D), jnp.bfloat16)
    # the pools donated, as the decoder's entry points donate them: an
    # undonated pool is copied once before the in-place write
    text = jax.jit(
        functools.partial(paged_attention_update, page_size=PAGE,
                          kind="decode", window=window),
        donate_argnums=(3, 4)).lower(
        sds((GQA_LANES, 1, GQA_HEADS, D), jnp.bfloat16), kv, kv, pool, pool,
        sds((GQA_LANES, width), jnp.int32), sds((GQA_LANES,), jnp.int32),
        sds((GQA_LANES, 1), jnp.bool_), sds((GQA_LANES, 1), jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert not [line for line in text.splitlines()
                if f"bf16[{pages},{PAGE},{GQA_KV_HEADS * D}]" in line
                and " copy(" in line]


@pytest.mark.parametrize("window", [None, WINDOW], ids=["full", "window"])
@pytest.mark.parametrize("rows,seq", [(4, 8192), (1, 2048)])
def test_grouped_prefill_compiles_for_both_kinds_of_layer(
        one_chip, compiled_kernels, window, rows, seq):
    """The prefill side: ``attention_bshd`` takes the forward-only flash
    kernel (a K/V head copied once for its 7 query heads; blocks wholly
    before a row's window skipped) at the largest and the smallest
    shape the kernel serves."""
    from paddle_tpu.ops.flash_attention import attention_bshd

    def sds(heads):
        return jax.ShapeDtypeStruct((rows, seq, heads, D), jnp.bfloat16,
                                    sharding=one_chip)

    _compile(functools.partial(attention_bshd, causal=True,
                               scale=D ** -0.5, window=window),
             sds(GQA_HEADS), sds(GQA_KV_HEADS), sds(GQA_KV_HEADS))


@pytest.mark.parametrize("tokens", [32, 4 * 8192],
                         ids=["decode", "prefill"])
def test_expert_layer_compiles_to_grouped_matmuls(one_chip, tokens):
    """``ops.moe.dropless_moe`` at the published widths (64 experts of
    2560 x 768, 6 a token): each of the three ``ragged_dot`` products
    becomes XLA's grouped-matmul kernel, which walks the sorted rows
    and reads the experts that own some: no dense product over all 64
    experts is in the program."""
    from paddle_tpu.ops.moe import dropless_moe
    hidden, experts, inter = 2560, 64, 768

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(dropless_moe, top_k=6)).lower(
        sds(tokens, hidden), sds(tokens, hidden), sds(hidden, experts),
        sds(experts, hidden, inter), sds(experts, hidden, inter),
        sds(experts, inter, hidden), valid=sds(tokens, dtype=jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-none"') == 3
    assert f"[{tokens * 6},{experts}," not in text


# ------ a share of the experts, groups of 8, a ring of 9 pages (PR 33) --
# the shared-expert configuration's serving geometry: 64 query heads
# over 8 K/V heads of 128, bf16 pools, 128 lanes; the one full layer's
# 257-page tables over 32,897 pages, a window layer's ring of 9 over
# 1 + 128 x 9
EP_HEADS, EP_KV_HEADS, EP_LANES, EP_WINDOW = 64, 8, 128, 128
EP_KINDS = {"full": (None, 257, 32897), "window": (EP_WINDOW, 9, 1153)}


@pytest.mark.parametrize("kind", sorted(EP_KINDS))
def test_decode_compiles_for_groups_of_eight_and_a_ring_of_nine(
        one_chip, compiled_kernels, kind):
    """``paged_attention_update`` as that configuration's decode step
    calls it: the page-copying kernel with 8 query heads a K/V head,
    128 lanes, over the whole context and over a ring of 9 pages; the
    pool reaches the kernel as it lies."""
    from paddle_tpu.ops.paged_attention import (paged_attention_update,
                                                ring_pages)
    window, width, pages = EP_KINDS[kind]
    assert ring_pages(EP_WINDOW, PAGE) == EP_KINDS["window"][1]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds(kv_pool_shape(pages, PAGE, EP_KV_HEADS, D), jnp.bfloat16)
    kv = sds((EP_LANES, 1, EP_KV_HEADS, D), jnp.bfloat16)
    text = jax.jit(
        functools.partial(paged_attention_update, page_size=PAGE,
                          kind="decode", window=window),
        donate_argnums=(3, 4)).lower(
        sds((EP_LANES, 1, EP_HEADS, D), jnp.bfloat16), kv, kv, pool, pool,
        sds((EP_LANES, width), jnp.int32), sds((EP_LANES,), jnp.int32),
        sds((EP_LANES, 1), jnp.bool_), sds((EP_LANES, 1), jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert not [line for line in text.splitlines()
                if f"bf16[{pages},{PAGE},{EP_KV_HEADS * D}]" in line
                and " copy(" in line]


@pytest.mark.parametrize("window", [None, EP_WINDOW],
                         ids=["full", "window"])
@pytest.mark.parametrize("rows,seq", [(16, 2048), (16, 1024)])
def test_prefill_of_many_heads_takes_the_kernel_where_scores_would_not_fit(
        one_chip, compiled_kernels, window, rows, seq):
    """16 rows of 64 heads: at 2,048 positions the flash kernel by
    length, at 1,024 because the dense path's scores would be 6 GiB
    (``flash_attention.DENSE_SCORES_BYTES``)."""
    from paddle_tpu.ops.flash_attention import attention_bshd

    def sds(heads):
        return jax.ShapeDtypeStruct((rows, seq, heads, D), jnp.bfloat16,
                                    sharding=one_chip)

    _compile(functools.partial(attention_bshd, causal=True,
                               scale=D ** -0.5, window=window),
             sds(EP_HEADS), sds(EP_KV_HEADS), sds(EP_KV_HEADS))


@pytest.mark.parametrize("tokens,block", [(128, 0), (16 * 2048, 4096)],
                         ids=["decode", "prefill-in-blocks"])
def test_a_share_of_the_experts_compiles_to_grouped_matmuls(
        one_chip, tokens, block):
    """``ops.moe.dropless_moe`` at the published widths of the share: a
    router of 128 outputs, 16 experts of 6144 x 2048 held, 8 a token by
    sigmoid scores, a shared expert; the products over the held experts
    are XLA's grouped matmuls over ``tokens * 8`` sorted rows (a block
    of 4,096 tokens at a time in a long prefill, whose temporaries are
    then a block's), and no product is as wide as the router. Each
    product is there twice: over the capacity's quarter of the rows
    (twice the 16/128 even routing gives) and, where more rows are the
    held experts', over all of them."""
    from paddle_tpu.ops.moe import dropless_moe
    hidden, routed, held, inter = 6144, 128, 16, 2048

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(
        dropless_moe, top_k=8, scoring="sigmoid_norm", scale=2.5,
        activation="silu", token_block=block)).lower(
        sds(tokens, hidden), sds(tokens, hidden), sds(hidden, routed),
        sds(held, hidden, inter), sds(held, hidden, inter),
        sds(held, inter, hidden), valid=sds(tokens, dtype=jnp.bool_),
        shared=(sds(hidden, inter), sds(hidden, inter), sds(inter, hidden))
    ).compile()
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-none"') == 6
    rows = (block or tokens) * 8
    assert f"bf16[{rows},{hidden}]" in text
    assert f"bf16[{rows // 4},{hidden}]" in text
    if block:
        assert f"bf16[{tokens * 8},{hidden}]" not in text
        # what it keeps beside its result is a block's, not the call's
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


# -- one mixer a layer: groups of 16, a state pool, latent experts (PR 35) --
# the hybrid configuration's serving geometry: 32 query heads over 2 K/V
# heads of 128 (a folded pool 256 lanes wide), bf16, 128 lanes, the one
# attention layer's 257-page tables over 32,897 pages; five state-space
# layers of 128 heads of 64 with a state of 128 over 129 slots; 128 held
# of 512 ungated relu^2 experts of 1024 x 2688, 22 a token
HY_HEADS, HY_KV_HEADS, HY_LANES, HY_WIDTH, HY_PAGES = 32, 2, 128, 257, 32897
HY_CUT = dict(num_layers=11, moe_num_experts=128, vocab_size=32768,
              dtype="bfloat16")
CHIP_BYTES = int(15.75 * 1024 ** 3)


def test_decode_compiles_for_groups_of_sixteen_over_a_256_lane_pool(
        one_chip, compiled_kernels):
    """``paged_attention_update`` as the hybrid configuration's decode
    step calls it: the page-copying kernel with 16 query heads a K/V
    head, 128 lanes, two K/V heads folded into 256 lanes; the pool
    reaches the kernel as it lies."""
    from paddle_tpu.ops.paged_attention import paged_attention_update

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shape = kv_pool_shape(HY_PAGES, PAGE, HY_KV_HEADS, D)
    assert shape[-1] == 256
    pool = sds(shape, jnp.bfloat16)
    kv = sds((HY_LANES, 1, HY_KV_HEADS, D), jnp.bfloat16)
    text = jax.jit(
        functools.partial(paged_attention_update, page_size=PAGE,
                          kind="decode", window=None),
        donate_argnums=(3, 4)).lower(
        sds((HY_LANES, 1, HY_HEADS, D), jnp.bfloat16), kv, kv, pool, pool,
        sds((HY_LANES, HY_WIDTH), jnp.int32), sds((HY_LANES,), jnp.int32),
        sds((HY_LANES, 1), jnp.bool_), sds((HY_LANES, 1), jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert not [line for line in text.splitlines()
                if f"bf16[{HY_PAGES},{PAGE},256]" in line
                and " copy(" in line]


@pytest.mark.parametrize("rows,seq", [(16, 2048), (1, 2048), (16, 1024),
                                      (1, 512)])
def test_prefill_of_groups_of_sixteen_takes_the_flash_kernel(
        one_chip, compiled_kernels, rows, seq):
    """The hybrid model asks for the kernel always
    (``use_flash="always"``): 16 rows at 1,024 positions keep 3 GiB of
    dense scores, under ``DENSE_SCORES_BYTES``, and the chip has no
    room for them beside that prefill's other layers; a caller that
    says ``True`` still gets the dense path below 2,048."""
    from paddle_tpu.ops.flash_attention import attention_bshd

    def sds(heads):
        return jax.ShapeDtypeStruct((rows, seq, heads, D), jnp.bfloat16,
                                    sharding=one_chip)

    args = (sds(HY_HEADS), sds(HY_KV_HEADS), sds(HY_KV_HEADS))
    _compile(functools.partial(attention_bshd, causal=True, scale=D ** -0.5,
                               window=None, use_flash="always"), *args)
    if seq < 2048:
        text = jax.jit(functools.partial(
            attention_bshd, causal=True, scale=D ** -0.5, window=None,
            use_flash=True)).lower(*args).compile().as_text()
        assert "tpu_custom_call" not in text


@pytest.mark.parametrize("tokens,block", [(128, 0), (16 * 2048, 2048)],
                         ids=["decode", "prefill-in-blocks"])
def test_ungated_latent_experts_compile_to_grouped_matmuls(
        one_chip, tokens, block):
    """``dropless_moe`` as the hybrid configuration's expert layer calls
    it for a prefill (2,048 tokens at a time in a long one), and at the
    rows of a decode step (whose 128 lanes the model itself computes
    unsorted: the test below): the router reads 4,096 columns, the
    experts a latent of 1,024; 128 held of 512, 22 a token, ungated:
    two grouped matmuls over ``tokens * 22`` sorted rows, and the same
    two over the capacity's half of them (twice the quarter even
    routing gives)."""
    from paddle_tpu.ops.moe import dropless_moe
    hidden, latent, routed, held, inter = 4096, 1024, 512, 128, 2688

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda x, r, wr, w1, w2, valid: dropless_moe(
            x, r, wr, None, w1, w2, top_k=22, scoring="sigmoid_norm",
            scale=5.0, activation="relu2", token_block=block, valid=valid)
    ).lower(sds(tokens, latent), sds(tokens, hidden), sds(hidden, routed),
            sds(held, latent, inter), sds(held, inter, latent),
            sds(tokens, dtype=jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot-none"') == 4
    rows = (block or tokens) * 22
    assert f"bf16[{rows},{latent}]" in text
    assert f"bf16[{rows // 2},{latent}]" in text
    if block:
        assert f"bf16[{tokens * 22},{latent}]" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


@pytest.mark.parametrize("program", ["decode", (16, 2048), (16, 1024)],
                         ids=["decode-128", "prefill-16x2048",
                              "prefill-16x1024"])
def test_the_hybrid_cells_programs_fit_the_chip_beside_their_pools(
        one_chip, compiled_kernels, program):
    """The decode program, the largest prefill program and the one the
    compiler refused while its attention was dense ("Used 15.86G of
    15.75G hbm") of the cell ``serve-nemotron3-reasoning`` (11 layers,
    128 held experts, a quarter of the vocabulary, bfloat16; abstract
    weights), compiled
    for the chip over donated pools of the cell's sizes: arguments and
    temporaries together stay under the chip's 15.75 GiB, the decode
    step keeps nothing of a state pool's size beside the pools (the
    states are advanced where they lie), and both hold the attention
    layer's kernel."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    with paddle.LazyGuard():
        model = models.NemotronHForCausalLM(
            models.nemotron_3_super_120b_a12b(**HY_CUT))
    model.eval()
    width = HY_WIDTH + 1                    # the table, then the state slot
    dec = CachedDecoder(model, max_batch=HY_LANES, page_size=PAGE,
                        pages_per_seq=width, donate=True,
                        max_positions=4112, kv_dtype="")
    assert dec.use_pallas is True

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    params, buffers = described(state_arrays(model))
    k, v = described(jax.eval_shape(lambda: model.init_kv_pools(
        HY_PAGES, PAGE, state_slots=1 + HY_LANES)))
    assert tuple(v[0].shape) == (129, 128, 64, 128) \
        and v[0].dtype == jnp.float32
    if program == "decode":
        rows = HY_LANES
        lowered = dec._decode_jit.lower(
            params, buffers, sds((rows,), jnp.int64), sds((rows,), jnp.int32),
            sds((rows,), jnp.bool_), sds((rows,), jnp.int32),
            sds((rows, width), jnp.int32), sds((rows,), jnp.float32),
            sds((rows,), jnp.float32), k, v)
    else:
        rows, seq = program
        lowered = dec._prefill_jit.lower(
            params, buffers, sds((rows, seq), jnp.int64),
            sds((rows,), jnp.int32), sds((rows, width), jnp.int32),
            sds((rows,), jnp.float32), sds((rows,), jnp.float32), k, v)
    compiled = lowered.compile()
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes \
        < CHIP_BYTES
    # the pools are written where they lie
    assert plan.alias_size_in_bytes > 3 * 1024 ** 3
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "ssm/" in text
    # 128 lanes x 22 reach the router's 512 outputs: a decode step
    # computes every held expert, a prefill sorts its rows by expert
    assert ("ragged-dot" in text) == (program != "decode")
    if program == "decode":
        assert plan.temp_size_in_bytes < 512 * 1024 ** 2
        assert "ssm/state_update" in text


# -- latent attention: one 576-value row a token for 20 heads -------------
# the latent configuration's serving geometry: 20 heads whose absorbed
# queries are 512 + 64 wide, a row of 640 lanes (whole tiles), bf16,
# 128 lanes, 257-page tables over 32,897 pages; a share of 8 of 64
# SwiGLU experts of 2048 x 1536, 4 a token, a selection bias
LA_HEADS, LA_WIDTH, LA_LATENT, LA_LANES = 20, 576, 512, 128
LA_CUT = dict(num_layers=2, moe_num_experts=8, vocab_size=19360)


def test_latent_decode_compiles_over_a_640_lane_pool(one_chip,
                                                     compiled_kernels):
    """``paged_latent_attention_update`` as the latent configuration's
    decode step calls it: the page-copying kernel, 20 heads a lane in
    one MXU product, a 576-value row in 640 lanes; the pool reaches the
    kernel as it lies, row-major (a 576-lane pool is kept by the chip
    with its page axis minor, and copied twice round the kernel)."""
    from paddle_tpu.ops.paged_attention import (
        latent_pool_shape, paged_latent_attention_update)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    shape = latent_pool_shape(HY_PAGES, PAGE, LA_WIDTH)
    assert shape[-1] == 640
    text = jax.jit(
        functools.partial(paged_latent_attention_update, page_size=PAGE,
                          kind="decode", scale=1 / 16, value_dim=LA_LATENT),
        donate_argnums=(2,)).lower(
        sds((LA_LANES, 1, LA_HEADS, LA_WIDTH), jnp.bfloat16),
        sds((LA_LANES, 1, LA_WIDTH), jnp.bfloat16), sds(shape, jnp.bfloat16),
        sds((LA_LANES, HY_WIDTH), jnp.int32), sds((LA_LANES,), jnp.int32),
        sds((LA_LANES, 1), jnp.bool_), sds((LA_LANES, 1), jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    pool = rf"bf16\[{HY_PAGES},{PAGE},640\]"
    assert set(re.findall(pool + r"\{([\d,]+)", text)) == {"2,1,0"}
    assert not re.findall(pool + r"\S* copy\(", text)


@pytest.mark.parametrize("program", ["decode", (16, 2048)],
                         ids=["decode-128", "prefill-16x2048"])
def test_the_latent_cells_programs_compile_over_their_pools(
        one_chip, compiled_kernels, program):
    """The decode program and the largest prefill program of a two-layer
    cut of the cell ``serve-glm47flash-reasoning`` (a dense layer and an
    expert layer, abstract bfloat16 weights), compiled for the chip over
    donated latent pools of the cell's 32,897 pages: the pools are
    written where they lie and never copied, the decode step holds the
    latent kernel, the prefill the flash kernel at heads of 256, and
    the ``mla`` scopes name the projections."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    with paddle.LazyGuard():
        model = models.GPTForCausalLM(models.glm_4p7_flash(**LA_CUT))
    model.eval()
    dec = CachedDecoder(model, max_batch=LA_LANES, page_size=PAGE,
                        pages_per_seq=HY_WIDTH, donate=True,
                        max_positions=4112, kv_dtype="")
    assert dec.use_pallas is True

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def described(tree):
        return jax.tree_util.tree_map(lambda a: sds(
            a.shape, jnp.bfloat16 if jnp.issubdtype(a.dtype, jnp.floating)
            else a.dtype), tree)

    params, buffers = described(state_arrays(model))
    k, v = described(jax.eval_shape(lambda: model.init_kv_pools(
        HY_PAGES, PAGE)))
    assert v == [()] * 2
    width = HY_WIDTH
    if program == "decode":
        rows = LA_LANES
        lowered = dec._decode_jit.lower(
            params, buffers, sds((rows,), jnp.int32), sds((rows,), jnp.int32),
            sds((rows,), jnp.bool_), sds((rows,), jnp.int32),
            sds((rows, width), jnp.int32), sds((rows,), jnp.float32),
            sds((rows,), jnp.float32), k, v)
    else:
        rows, seq = program
        lowered = dec._prefill_jit.lower(
            params, buffers, sds((rows, seq), jnp.int64),
            sds((rows,), jnp.int32), sds((rows, width), jnp.int32),
            sds((rows,), jnp.float32), sds((rows,), jnp.float32), k, v)
    compiled = lowered.compile()
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < CHIP_BYTES
    assert plan.alias_size_in_bytes >= 2 * HY_PAGES * PAGE * 640 * 2
    text = compiled.as_text()
    pool = rf"bf16\[{HY_PAGES},{PAGE},640\]"
    assert set(re.findall(pool + r"\{([\d,]+)", text)) == {"2,1,0"}
    assert not re.findall(pool + r"\S* copy\(", text)
    assert "mla/" in text and "tpu_custom_call" in text
    if program == "decode":
        assert "mla/absorb" in text and "mla/v_up" in text
        assert plan.temp_size_in_bytes < 256 * 1024 ** 2


# -- power retention: a 32.5 MiB float32 state a lane a layer -------------
# the retention configuration's serving geometry: 8 K/V heads of 128 (so
# S [8, 8256, 128] and z [8, 65, 128] a slot), 5 query heads each, 32
# lanes over 33 slots, a table row of the slot alone, five layers
RT_LANES, RT_KV, RT_D, RT_BIG = 32, 8, 128, 8256


def test_retention_decode_kernel_compiles_at_the_cells_widths(
        one_chip, compiled_kernels):
    """``retention_decode_pools`` as the retention configuration's decode
    step calls it: one kernel a layer, both pools aliased in to out and
    never copied, nothing of a pool's size beside them."""
    from paddle_tpu.ops.retention import retention_decode_pools, slot_shapes
    s_shape, z_shape = slot_shapes(RT_KV, RT_D)
    assert s_shape == (RT_KV, RT_BIG, RT_D) and z_shape == (RT_KV, 65, RT_D)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda *a: retention_decode_pools(*a, use_pallas=True),
        donate_argnums=(6, 7)).lower(
            sds((RT_LANES, RT_KV, 5, RT_D), jnp.bfloat16),
            sds((RT_LANES, RT_KV, RT_D), jnp.bfloat16),
            sds((RT_LANES, RT_KV, RT_D), jnp.bfloat16),
            sds((RT_LANES, RT_KV), jnp.float32),
            sds((RT_LANES,), jnp.int32), sds((RT_LANES,), jnp.bool_),
            sds((1 + RT_LANES,) + s_shape, jnp.float32),
            sds((1 + RT_LANES,) + z_shape, jnp.float32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "retention/state_update" in text
    pool = rf"f32\[{1 + RT_LANES},{RT_KV},{RT_BIG},{RT_D}\]"
    assert not re.findall(pool + r"\S* copy\(", text)
    plan = compiled.memory_analysis()
    assert plan.alias_size_in_bytes > (1 + RT_LANES) * RT_KV * RT_BIG \
        * RT_D * 4
    assert plan.temp_size_in_bytes < 64 * 1024 ** 2


@pytest.mark.parametrize("program", ["decode", (16, 2048)],
                         ids=["decode-32", "prefill-16x2048"])
def test_the_retention_cells_programs_fit_the_chip_beside_their_pools(
        one_chip, compiled_kernels, program):
    """The decode program and the largest prefill program of the cell
    ``serve-brumby-backlog`` (five layers, abstract bfloat16 weights),
    compiled for the chip over donated state pools of 33 slots: weights,
    pools and temporaries stay under the chip's 15.75 GiB (a [16, 2048]
    window at once did not: its rows now go through the layers a block
    of 1,024 tokens at a time), the pools are written where they lie,
    the decode step holds the kernel and keeps nothing of a pool's size
    beside the pools."""
    import paddle_tpu as paddle
    from paddle_tpu import models
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    with paddle.LazyGuard():
        model = models.GPTForCausalLM(models.brumby_14b_base(num_layers=5))
    model.eval()
    assert model.prefill_block_tokens == 1024
    dec = CachedDecoder(model, max_batch=RT_LANES, page_size=PAGE,
                        pages_per_seq=1, donate=True, max_positions=4096,
                        kv_dtype="")
    assert dec.use_pallas is True

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    def described(tree, cast):
        return jax.tree_util.tree_map(lambda a: sds(
            a.shape, jnp.bfloat16 if cast and jnp.issubdtype(
                a.dtype, jnp.floating) else a.dtype), tree)

    params, buffers = described(state_arrays(model), True)
    k, v = described(jax.eval_shape(lambda: model.init_kv_pools(
        2, PAGE, state_slots=1 + RT_LANES)), False)
    assert tuple(k[0].shape) == (1 + RT_LANES, RT_KV, RT_BIG, RT_D)
    if program == "decode":
        rows = RT_LANES
        lowered = dec._decode_jit.lower(
            params, buffers, sds((rows,), jnp.int32), sds((rows,), jnp.int32),
            sds((rows,), jnp.bool_), sds((rows,), jnp.int32),
            sds((rows, 1), jnp.int32), sds((rows,), jnp.float32),
            sds((rows,), jnp.float32), k, v)
    else:
        rows, seq = program
        lowered = dec._prefill_jit.lower(
            params, buffers, sds((rows, seq), jnp.int64),
            sds((rows,), jnp.int32), sds((rows, 1), jnp.int32),
            sds((rows,), jnp.float32), sds((rows,), jnp.float32), k, v)
    compiled = lowered.compile()
    plan = compiled.memory_analysis()
    assert plan.argument_size_in_bytes + plan.temp_size_in_bytes < CHIP_BYTES
    assert plan.alias_size_in_bytes > 5 * 1024 ** 3
    text = compiled.as_text()
    pool = rf"f32\[{1 + RT_LANES},{RT_KV},{RT_BIG},{RT_D}\]"
    assert not re.findall(pool + r"\S* copy\(", text)
    assert "retention/qkv" in text and "retention/out" in text
    if program == "decode":
        assert text.count("tpu_custom_call") == 5      # one a layer
        assert plan.temp_size_in_bytes < 256 * 1024 ** 2
    else:
        assert "retention/chunk_scan" in text
        assert plan.temp_size_in_bytes < 2 * 1024 ** 3
