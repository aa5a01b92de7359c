"""Flash attention for TPU.

Replaces the reference's CUDA flash_attn binding
(/root/reference/paddle/phi/backends/dynload/flashattn.cc). A Pallas kernel
implementation lands behind `flash_attention_bshd`; `supported()` gates usage
by platform/shape so callers can fall back to the XLA softmax path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..framework import place as _place


# what the dense path may keep of scores and probabilities: a quarter of
# a v5e's memory. The largest dense prefill of the benchmark's GPT and
# SmallThinker servers keeps 2.25 GiB (float32, 32 rows of 16 heads at
# 768 positions)
DENSE_SCORES_BYTES = 4 << 30


def preferred(q, k, v, mask, causal) -> bool:
    """supported() AND long enough that the kernel beats XLA attention.

    Below FLAGS_flash_min_seqlen (default 2048, framework/flags.py) the
    XLA softmax path wins end-to-end on this chip (measured, PERF.md:
    gpt2-medium s=512 trains at 40.8% vs 30.6% MFU, s=1024 at 33.2% vs
    24.3%); the kernel's O(S) memory only pays for itself once the
    sq*sk materialization stops fitting HBM (dense s=2048 b=4 OOMs) —
    hence the gate uses the longer of the two sequence lengths. A
    shorter call whose scores would not fit either takes the kernel
    too: the dense path keeps ``b * h * sq * sk`` float32 scores and
    their probabilities in the inputs' type, and 16 rows of 64 heads at
    1,024 positions are 6 GiB of them (a served model of many heads
    under a prefill budget counted in tokens)."""
    if not supported(q, k, v, mask, causal):
        return False
    from ..framework.flags import flag_value
    b, sq, h, _ = q.shape
    if b * h * sq * k.shape[1] * (4 + q.dtype.itemsize) > DENSE_SCORES_BYTES:
        return True
    return max(sq, k.shape[1]) >= int(flag_value("FLAGS_flash_min_seqlen"))


def supported(q, k, v, mask, causal) -> bool:
    if mask is not None:
        return False
    if not _place.on_tpu():
        return False
    # block constraints: seq multiple of 128, head_dim in {64,128,256}
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if d not in (64, 128, 256):
        return False
    if sq % 128 != 0 or sk % 128 != 0:
        return False
    if causal and sq != sk:
        return False  # kernel masks top-left aligned; see _check_mha_args
    return True


def _default_blocks(sq, sk):
    """Untuned default blocks for traced calls: large tiles keep the MXU
    busy and amortize the per-tile online-softmax rescaling (the 128×128
    default measured ~11% attention efficiency on the 1.3B config —
    attention was 39%% of the whole step, tools/ablate_13b.py)."""
    bq = 512 if sq % 512 == 0 else (256 if sq % 256 == 0 else 128)
    bk = 1024 if sk % 1024 == 0 else (512 if sk % 512 == 0 else 128)
    return bq, bk


def clip_blocks(bq, bk, sq, sk):
    """Shrink (bq, bk) to divisors of the sequence lengths, flooring at
    the 128-lane tile. Shared by the main flash dispatch and the ring
    chunks so block-selection constraints can't diverge."""
    while sq % bq and bq > 128:
        bq //= 2
    while sk % bk and bk > 128:
        bk //= 2
    return bq, bk


def _block_candidates(sq, sk):
    """Valid (block_q, block_k) choices for the autotuner (multiples of
    128 that divide the sequence lengths). Long sequences admit larger
    tiles — at s=4096/8192 the online-softmax rescaling amortizes over
    bigger K spans and the 512x1024 global default stops being optimal
    (round-4 verdict item 3); VMEM-infeasible candidates fail to compile
    and are skipped by autotune.pick."""
    bqs = [128, 256, 512] + ([1024] if sq >= 4096 else [])
    bks = [128, 256, 512, 1024] + ([2048] if sk >= 8192 else [])
    cands = [(bq, bk) for bq in bqs for bk in bks
             if sq % bq == 0 and sk % bk == 0]
    return cands or [(128, 128)]


def pretune(batch, num_heads, seq_len, head_dim, dtype="bfloat16",
            causal=True, kv_len=None):
    """Eagerly autotune flash block sizes for one attention shape by
    timing the WHOLE fwd+bwd step per candidate on the real device, and
    persist the winner ("mha_step" cache) where the traced dispatch will
    find it. Call before compiling a TrainStep on a long-context config —
    the autotuner cannot time inside a trace (perf-lessons), so without
    pre-tuning traced calls fall back to the static default."""
    from . import autotune
    from .pallas_attention import mha

    if not autotune.enabled():
        return None
    sk = kv_len or seq_len
    cands = _block_candidates(seq_len, sk)
    if len(cands) <= 1:
        return cands[0]
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (batch, num_heads, seq_len, head_dim)
    qt = jax.random.normal(kq, shape, jnp.float32).astype(dtype)
    kt = jax.random.normal(kk, (batch, num_heads, sk, head_dim),
                           jnp.float32).astype(dtype)
    # V must be a DISTINCT buffer: vt = kt would let each candidate read
    # one K/V array instead of two, so the timed memory traffic (and the
    # measured ranking, on bandwidth-bound long-context shapes) would
    # diverge from real two-buffer workloads
    vt = jax.random.normal(kv, (batch, num_heads, sk, head_dim),
                           jnp.float32).astype(dtype)
    s = 1.0 / math.sqrt(head_dim)

    def make_fn(c):
        def step(a, x, y):
            def loss(a, x, y):
                return jnp.sum(mha(a, x, y, causal, s, c[0], c[1])
                               .astype(jnp.float32))
            g = jax.grad(loss, argnums=(0, 1, 2))(a, x, y)
            return g
        return jax.jit(step)

    return autotune.pick(
        "mha_step",
        (batch, num_heads, seq_len, sk, head_dim, str(qt.dtype), causal),
        cands, make_fn, (qt, kt, vt))


def flash_attention_bshd(q, k, v, causal=False, scale=None, window=None):
    """[B,S,H,D] layout wrapper over the BHSD pallas kernel; block sizes
    are autotuned per shape on the first real-device call
    (ops/autotune.py — the reference's phi/kernels/autotune analog).
    Fewer K/V heads than query heads, or a ``window``, take the
    forward-only kernel (``pallas_attention.mha_forward``) at the
    static blocks."""
    from . import autotune
    from .pallas_attention import mha
    if window is not None or k.shape[2] != q.shape[2]:
        from .pallas_attention import mha_forward
        if not causal:
            raise ValueError("grouped heads and windows are causal")
        sq = q.shape[1]
        bq, bk = clip_blocks(*_default_blocks(sq, sq), sq, sq)
        with jax.named_scope("flash_attention"):
            out = mha_forward(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), sm_scale=scale if scale is not None
                else 1.0 / math.sqrt(q.shape[-1]), block_q=bq, block_k=bk,
                window=window)
        return jnp.swapaxes(out, 1, 2)

    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    cands = _block_candidates(sq, sk)
    if len(cands) > 1 and autotune.enabled() and not isinstance(
            qt, jax.core.Tracer):
        bq, bk = autotune.pick(
            "mha_fwd", (b, h, sq, sk, d, str(qt.dtype), causal), cands,
            lambda c: jax.jit(lambda a, x, y: mha(
                a, x, y, causal, s, c[0], c[1])),
            (qt, kt, vt))
    else:
        # traced call: can't time here — use a prior (possibly on-disk)
        # tuning result for this shape (fwd+bwd "mha_step" pretune wins
        # over a fwd-only result), an explicit flag override, else the
        # measured-good default (512, 1024 capped to the sequence)
        from ..framework.flags import flag_value
        shape_key = (b, h, sq, sk, d, str(qt.dtype), causal)
        hit = autotune.cached("mha_step", shape_key) or \
            autotune.cached("mha_fwd", shape_key)
        fq = int(flag_value("FLAGS_flash_block_q"))
        fk = int(flag_value("FLAGS_flash_block_k"))
        if fq or fk:
            bq, bk = (fq or 128), (fk or 128)
        elif hit:
            bq, bk = hit
        else:
            bq, bk = _default_blocks(sq, sk)
        # shrink to divisors of the sequence (supported() guarantees
        # seq % 128 == 0, so the halving bottoms out at >= 128)
        bq, bk = clip_blocks(bq, bk, sq, sk)
    # metadata only: names the kernel's device time in a profile (the
    # backward pass is scoped where it is traced, pallas_attention.py)
    with jax.named_scope("flash_attention"):
        out = mha(qt, kt, vt, causal=causal, sm_scale=s, block_q=bq,
                  block_k=bk)
    return jnp.swapaxes(out, 1, 2)


def attention_bshd(q, k, v, causal=False, scale=None, use_flash=True,
                   window=None):
    """THE flash-or-dense selection point for maskless attention in
    [B,S,H,D] layout, for the module attention path and the stacked SPMD
    decoder alike: the Pallas kernel when ``use_flash`` and preferred()
    (supported shapes AND seq >= FLAGS_flash_min_seqlen, the measured
    boundary, PERF.md) or ``use_flash="always"`` and supported(), else
    the XLA softmax reference. ``k``/``v`` may hold fewer heads than
    ``q`` (K/V head ``g`` serves query heads ``g*G .. g*G+G-1``); a row
    sees its last ``window`` positions (causal only), itself included."""
    if use_flash and (preferred(q, k, v, None, causal) or (
            use_flash == "always" and supported(q, k, v, None, causal))):
        return flash_attention_bshd(q, k, v, causal=causal, scale=scale,
                                    window=window)
    if window is not None or k.shape[2] != q.shape[2]:
        return _dense_grouped(q, k, v, causal, scale, window)
    # dense path: matmuls stay in the INPUT dtype (bf16 under AMP — the
    # MXU fast path; _mha_reference is the f32-matmul test oracle and
    # routing production traffic through it cost 24% of the train step),
    # only the softmax accumulates in f32
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * \
        jnp.asarray(s, qt.dtype)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, jnp.asarray(-1e30, logits.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(qt.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def _dense_grouped(q, k, v, causal, scale, window):
    """The dense path for grouped K/V heads and sliding windows:
    matmuls in the input dtype, scores and softmax in f32."""
    if window is not None and not causal:
        raise ValueError("a window is causal")
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, hk, h // hk, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * jnp.float32(s)
    if causal:
        rows = jnp.arange(sq)[:, None] + (sk - sq)
        cols = jnp.arange(sk)[None, :]
        seen = rows >= cols
        if window is not None:
            seen = seen & (rows - cols < window)
        logits = jnp.where(seen, logits, jnp.float32(-1e30))
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, h, d)
