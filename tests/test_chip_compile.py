"""The kernels of the main path, compiled for the chip without the chip.

The TPU's compiler is installed here and compiles for a device that is
described, not attached (``jax.experimental.topologies``): what it
refuses — a block that is not a legal tile, a slice off the tiling, a
tile too large for VMEM — it refuses here, at gpt3_1p3b widths, in a
second or two per kernel, where interpret mode accepts anything.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU's library at a time, every
xdist worker imports this file, and only the worker that is given it
may make the call. All of these tests live in this one file for the
same reason. Kernel selection asks ``framework.place.on_tpu``, which
sees this process's CPU; the ``compiled_kernels`` fixture steers it
from here.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# gpt3_1p3b attention geometry and the serving engine's defaults
H, D, B, PAGE, SLOTS = 16, 128, 8, 16, 2048


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


def test_prefill_flash_compiles(one_chip, compiled_kernels):
    """Serving prefill routes a 128-multiple window onto the flash
    kernel ([B, S, H, D] layout)."""
    from paddle_tpu.ops.pallas_paged_attention import prefill_flash
    x = jax.ShapeDtypeStruct((2, 256, H, D), jnp.bfloat16,
                             sharding=one_chip)
    _compile(functools.partial(prefill_flash, scale=D ** -0.5), x, x, x)


# ------------------------------------------------------ paged attention
def _paged_args(one_chip, kind, q_dtype, pool, seq):
    pages = SLOTS // PAGE
    num_pages = 1 + B * pages

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if pool == "int8":
        kv = (sds((num_pages, PAGE, H, D), jnp.int8),
              sds((num_pages, PAGE, H), jnp.float32))
    else:
        kv = sds((num_pages, PAGE, H, D), pool)
    s = 1 if kind == "decode" else seq
    return (sds((B, s, H, D), q_dtype), kv, kv,
            sds((B, pages), jnp.int32), sds((B,), jnp.int32),
            sds((B, s), jnp.int32), sds((B, s), jnp.int32))


POOLS = [(jnp.float32, jnp.float32), (jnp.bfloat16, jnp.bfloat16),
         (jnp.float32, "int8"), (jnp.bfloat16, "int8")]


@pytest.mark.parametrize(
    "q_dtype,pool", POOLS,
    ids=["f32", "bf16", "f32-q-int8-pool", "bf16-q-int8-pool"])
@pytest.mark.parametrize("kind,seq", [("decode", 1), ("chunked", 64),
                                      ("chunked", 5)])
def test_paged_attention_compiles(one_chip, compiled_kernels, kind, seq,
                                  q_dtype, pool):
    """The fused read-through-table kernel at gpt3_1p3b widths (16
    heads of 128, batch 8, 2048-slot tables of 16-slot pages): decode,
    a suffix-prefill window, and a speculative-verify window no
    8-multiple divides — over f32, bf16 and int8 pools, with the
    blocks ``autotune.paged_blocks`` picks unaided."""
    from paddle_tpu.ops.pallas_paged_attention import (paged_attention,
                                                       supported)
    args = _paged_args(one_chip, kind, q_dtype, pool, seq)
    assert supported(args[0], args[1], args[3], PAGE, kind)
    _compile(functools.partial(paged_attention, page_size=PAGE, kind=kind,
                               scale=D ** -0.5), *args)


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
    message on the chip — there is no interpret mode to fall back to."""
    from paddle_tpu.ops.pallas_paged_attention import paged_attention
    args = _paged_args(one_chip, "decode", *POOLS[1], 1)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        jax.jit(functools.partial(
            paged_attention, page_size=PAGE, kind="decode",
            scale=D ** -0.5, block_h=1)).lower(*args)
