"""Jitted prefill/decode steps over a cache-capable causal-LM Layer.

``CachedDecoder`` functionalizes the model once (``jit.functional``),
then exposes four device entry points. Three of them choose the next
token themselves: beside their feeds they take ``temperature [rows]``
and ``uniform [rows]`` (float32; a row at temperature 0 takes the
``argmax`` of its logits, first index on ties, a row above 0 the
inverse CDF of ``softmax(logits / t)`` at its uniform, the rule of
``sampling.py``; ``None`` for either is all rows greedy) and return
``(tokens [rows] int32, logits [rows, vocab], k', v', new_signature)``,
all device arrays: a caller that wants the tokens alone fetches
``rows * 4`` bytes and leaves the logits where they are.

- ``prefill(ids, prompt_lens, tables, temperature, uniform, pools)`` —
  one forward over a padded prompt window that writes the prompt's K/V
  into the paged pools and computes only the last real position's
  logits: the head is applied to that position's hidden state alone
  (``GPTKVCache.logits_at``), so no ``[B, S, vocab]`` array exists in
  the program;
- ``decode(tokens, positions, active, ctx, tables, temperature,
  uniform, pools)`` — the fixed-shape ``[max_batch, 1]`` decode step:
  append one position per live lane, attend through the block tables.
  ``tokens`` is ``[max_batch]`` int32, the type the step returns, so one
  step's choice is the next step's operand where it lies, on the
  device; a host array is cast to the same type, and both forms are one
  signature and one executable. ``lane_tokens(carried, fresh)`` writes
  the tokens the host knows (a prefill's choice) over such a vector
  without fetching it;
- ``prefill_chunked(ids, start, seg_lens, tables, temperature,
  uniform, pools)`` — suffix prefill at a per-row starting position:
  window tokens attend to the already-cached prefix through the block
  tables (kind="chunked"), used after a shared-prefix cache hit so only
  the unique suffix pays prefill; the last real position's logits like
  ``prefill``;
- ``verify(tokens, start, seg_lens, tables, pools)`` — the
  speculative-decoding verify step: ONE fixed-shape
  ``[max_batch, spec_k + 1]`` chunked forward scoring a draft model's
  proposed tokens, returning ALL window logits ``[B, S, vocab]`` (and
  no tokens: ``(logits, k', v', new_signature)``) so the host can run
  accept-and-resample.

The sampled rule sits under one ``lax.cond`` on "any row above 0"
inside the same executable: a batch of greedy rows pays for an argmax,
and there is one executable a signature whatever the requests ask for.

All are ``jax.jit``-compiled with the KV pools donated on backends
that support donation (the pools update in place on device), and both
consult the persistent compile cache (PR 5) first: on a warm
``FLAGS_compile_cache_dir`` the first dispatch of a signature loads a
ready AOT executable instead of tracing + compiling.
"""
from __future__ import annotations

import hashlib
import inspect
import json
from typing import Dict, Optional, Tuple

import numpy as np

from ...profiler import RecordEvent

__all__ = ["CachedDecoder", "supports_cached_decode"]


def supports_cached_decode(model) -> bool:
    """True when ``model.forward`` accepts a ``cache`` argument and the
    model can build its own paged pools — the duck-typed contract the
    decode engine and the hybrid-parallel generate helper key on."""
    fwd = getattr(model, "forward", None)
    if fwd is None or not callable(getattr(model, "init_kv_pools", None)):
        return False
    try:
        return "cache" in inspect.signature(fwd).parameters
    except (TypeError, ValueError):  # builtins / C-level callables
        return False


class CachedDecoder:
    """Prefill/decode dispatch for one model instance.

    ``page_size``/``pages_per_seq`` fix the block-table geometry
    (``T = pages_per_seq * page_size`` gathered context slots);
    ``max_batch`` fixes the decode-step shape. The caller owns the pool
    pytree (see ``PagedKVCache``) and threads it through every call —
    when ``donate`` is active the passed-in pools are consumed and MUST
    be replaced by the returned ones.

    Not thread-safe against concurrent mutation of the model's
    parameters (the engine snapshots them here at construction).
    """

    def __init__(self, model, *, max_batch: int, page_size: int,
                 pages_per_seq: int, donate: Optional[bool] = None,
                 max_positions: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 kv_dtype: Optional[str] = None, mesh=None):
        import jax

        from ...framework.flags import flag_value
        from ...jit.functional import state_arrays
        from ...models.gpt import GPTKVCache
        from ..mesh import ServingMesh

        if not supports_cached_decode(model):
            raise TypeError(
                f"{type(model).__name__} does not support KV-cached "
                f"decode (forward must accept cache=, and the model "
                f"must expose init_kv_pools)")
        self.model = model
        self.max_batch = int(max_batch)
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        # the replica's tensor-parallel mesh (serving/mesh.py): weights
        # shard by the shard.py rule tables, pools along the heads axis.
        # An inert mesh (None or 1 device) leaves EVERYTHING on the
        # single-shard path byte-for-byte — fingerprints, cache keys,
        # placement (regression-tested).
        smesh = mesh if isinstance(mesh, ServingMesh) else ServingMesh(mesh)
        spec = model.kv_cache_spec()
        # the pools shard by K/V heads, which a model may have fewer of
        smesh.validate_heads(int(spec.get("num_kv_heads",
                                          spec["num_heads"])))
        self.serving_mesh = smesh
        # pinned at construction (both join the geometry fingerprint,
        # so warmup manifests and the persistent compile cache key on
        # them too). Who attends is not an operator's choice: None
        # takes the fused paged kernels on a TPU and the pure-JAX body
        # elsewhere (ops.paged_attention.kernel_by_default); True /
        # False name one, for tests, oracles and rehearsals
        from ...ops.paged_attention import kernel_by_default
        self.use_pallas = bool(
            kernel_by_default(self.page_size)
            if use_pallas is None else use_pallas)
        self.kv_dtype = str(
            flag_value("FLAGS_decode_kv_dtype")
            if kv_dtype is None else kv_dtype) or ""
        self.max_positions = int(
            max_positions if max_positions is not None
            else model.kv_cache_spec()["max_seq_len"])
        # a layer that keeps a recurrent state a sequence (kv_cache.py,
        # the 'state' kind) cannot continue a window from its slot
        self.has_state = "state" in (spec.get("kinds") or {})
        # nor can a latent pool take a window (no chunked attention
        # over it is built)
        from .kv_cache import latent_of
        self.has_latent = bool(latent_of(spec))
        self._params, self._buffers = state_arrays(model)
        if smesh.live:
            # committed mp-sharded placement: GSPMD partitions every
            # entry point from these operand layouts — no in_shardings
            # needed on the jits
            self._params, self._buffers = smesh.place_state(
                self._params, self._buffers, model=model)
        self._donate = bool(donate) if donate is not None \
            else jax.default_backend() != "cpu"
        self._fp: Optional[str] = None
        # what the last program counted beside its logits (``_aux_out``:
        # device scalars; {} for a model without experts, and after a
        # chunked prefill or a verify step, which count nothing)
        self.last_aux: dict = {}
        # the last program's executable call, on the host's clock: the
        # ``decoder::launch`` span's length
        self.last_launch_s = 0.0
        # per-signature AOT memo; False marks "tried, unavailable"
        self._aot: Dict[tuple, object] = {}
        self.compiled_signatures = set()    # (site, shape-sig) seen
        # xstats memo: (site, shape-sig) -> ExecEntry
        self._xstats_entries: Dict[tuple, object] = {}

        _Tensor = None

        def _wrap(a):
            nonlocal _Tensor
            if _Tensor is None:
                from ...core.tensor import Tensor
                _Tensor = Tensor
            return _Tensor(a, stop_gradient=True)

        import jax.numpy as jnp

        from ...jit.functional import functional_call

        page = self.page_size
        use_pallas = self.use_pallas
        max_pos = self.max_positions
        # threaded into the traced fns: pool-entry constraints + the
        # per-shard Pallas dispatch (GPTKVCache.mesh); None when inert
        live_mesh = smesh.mesh if smesh.live else None

        from ...distributed.shard import constrain_batch

        def _one_position(logits, idx):
            """``[B, vocab]`` from a model that honoured
            ``cache.logits_at`` (``[B, 1, vocab]``), or from one that
            computed every position (``[B, S, vocab]``: a model of
            another family whose forward ignores the field)."""
            if logits.shape[1] == 1:
                return logits[:, 0]
            at = jnp.broadcast_to(idx[:, None, None],
                                  (logits.shape[0], 1, logits.shape[-1]))
            return jnp.take_along_axis(logits, at, axis=1)[:, 0]

        def _aux_out(aux):
            """What the forward counted beside the logits, as whole
            numbers fetched with them, each summed over the expert
            layers: assignments, experts that got a row, and the rows
            of each layer's fullest expert (over assignments / experts
            it says how uneven the routing is); where the layers hold a
            share of their experts, also the assignments the held ones
            computed and the layers whose grouped products were handed
            the capacity's rows alone or all of them. Empty for a model
            that counts nothing: the program then has no such output."""
            if not aux or "moe" not in aux:
                return {}
            per_layer = jnp.sum(jnp.stack(aux["moe"]), axis=0)  # [3 | 6]
            out = {"moe_assignments": per_layer[0],
                   "moe_experts_touched": per_layer[1],
                   "moe_max_expert_load": per_layer[2]}
            if per_layer.shape[0] > 3:
                out["moe_local_assignments"] = per_layer[3]
                out["moe_narrow_calls"] = per_layer[4]
                out["moe_wide_calls"] = per_layer[5]
            return out

        def _select(logits, temperature, uniform):
            """The token each row of ``logits`` ``[B, vocab]`` takes,
            ``[B]`` int32: the argmax (first index on ties) where the
            row's temperature is 0, else the first index whose
            cumulative mass of ``softmax(logits / t)`` exceeds the
            row's uniform times the whole (``sampling.py``'s rule, in
            float32). The sampled rule runs only when some row asks
            for it."""
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            hot = temperature > 0

            def sampled():
                z = logits.astype(jnp.float32) \
                    / jnp.where(hot, temperature, 1.0)[:, None]
                cdf = jnp.cumsum(
                    jnp.exp(z - jnp.max(z, axis=-1, keepdims=True)),
                    axis=-1)
                u = uniform * cdf[:, -1]
                # the count of entries at or under u is that first
                # index; a uniform that rounds up to 1 takes the last
                pick = jnp.minimum(jnp.sum(cdf <= u[:, None], axis=-1),
                                   logits.shape[-1] - 1)
                return jnp.where(hot, pick.astype(jnp.int32), greedy)

            return jax.lax.cond(jnp.any(hot), sampled, lambda: greedy)

        def _make_fns(use_pallas):
            # One closure set per kernel path. The real jits below bind
            # the pinned ``self.use_pallas``; the shadow-verification
            # oracle (observability.numerics) rebinds
            # ``use_pallas=False`` to get the pure-JAX reference
            # implementation without touching any dispatch state.

            def _prefill(params, buffers, ids, prompt_lens, tables,
                         temperature, uniform, k, v):
                # unified-surface batch pin: under a dp serving mesh
                # the prefill window shards by request row; meshless
                # (the single-replica engine default) this is the
                # identity
                ids = constrain_batch(ids)
                # heads-axis pin on the pool operands: GSPMD must never
                # gather a pool (identity when the mesh is inert)
                k = smesh.constrain_pools(k)
                v = smesh.constrain_pools(v)
                b, s = ids.shape
                positions = jnp.broadcast_to(
                    jnp.arange(s, dtype=jnp.int32), (b, s))
                valid = positions < prompt_lens[:, None]
                # only the last REAL position's logits are computed
                idx = jnp.clip(prompt_lens.astype(jnp.int32) - 1, 0,
                               s - 1)
                cache = GPTKVCache(
                    "prefill", page,
                    jax.tree_util.tree_map(_wrap, k),
                    jax.tree_util.tree_map(_wrap, v),
                    _wrap(tables), _wrap(prompt_lens), _wrap(valid),
                    _wrap(positions), use_pallas=use_pallas,
                    mesh=live_mesh, logits_at=_wrap(idx), aux={})
                logits, (k2, v2) = functional_call(
                    model, params, buffers, ids, cache=cache,
                    training=False)
                # tied lm_head leaves logits vocab-sharded under mp:
                # gather ONCE inside the executable, not on the host
                last = smesh.replicate(_one_position(logits, idx))
                return (_select(last, temperature, uniform), last, k2,
                        v2, _aux_out(cache.aux))

            def _decode(params, buffers, tokens, positions, active,
                        ctx, tables, temperature, uniform, k, v):
                tokens = constrain_batch(tokens)
                k = smesh.constrain_pools(k)
                v = smesh.constrain_pools(v)
                b = tokens.shape[0]
                ids = tokens[:, None]
                cache = GPTKVCache(
                    "decode", page,
                    jax.tree_util.tree_map(_wrap, k),
                    jax.tree_util.tree_map(_wrap, v),
                    _wrap(tables), _wrap(ctx), _wrap(active[:, None]),
                    _wrap(positions[:, None].astype(jnp.int32)),
                    use_pallas=use_pallas, mesh=live_mesh, aux={})
                logits, (k2, v2) = functional_call(
                    model, params, buffers, ids, cache=cache,
                    training=False)
                last = smesh.replicate(logits[:, 0])
                return (_select(last, temperature, uniform), last, k2,
                        v2, _aux_out(cache.aux))

            def _chunked(params, buffers, ids, start, seg_lens, tables,
                         k, v, logits_at=None):
                # suffix prefill / speculative verify window: per-row
                # starting positions; attention reaches the cached
                # prefix through the block tables (kind="chunked").
                # Returns ALL window logits [B, S, vocab], or those of
                # the one position a row that ``logits_at`` names.
                ids = constrain_batch(ids)
                k = smesh.constrain_pools(k)
                v = smesh.constrain_pools(v)
                b, s = ids.shape
                offs = jnp.arange(s, dtype=jnp.int32)[None, :]
                positions = start.astype(jnp.int32)[:, None] + offs
                # positions past the model's addressable range (a
                # verify window overhanging the budget) write to the
                # trash page and mask themselves out; their logits are
                # garbage the host never consumes
                valid = (offs < seg_lens[:, None]) & (positions < max_pos)
                ctx = (start + seg_lens).astype(jnp.int32)
                cache = GPTKVCache(
                    "chunked", page,
                    jax.tree_util.tree_map(_wrap, k),
                    jax.tree_util.tree_map(_wrap, v),
                    _wrap(tables), _wrap(ctx), _wrap(valid),
                    _wrap(positions), use_pallas=use_pallas,
                    mesh=live_mesh, logits_at=None if logits_at is None
                    else _wrap(logits_at))
                logits, (k2, v2) = functional_call(
                    model, params, buffers, ids, cache=cache,
                    training=False)
                return smesh.replicate(logits), k2, v2

            def _prefill_chunked(params, buffers, ids, start, seg_lens,
                                 tables, temperature, uniform, k, v):
                idx = jnp.clip(seg_lens.astype(jnp.int32) - 1, 0,
                               ids.shape[1] - 1)
                logits, k2, v2 = _chunked(params, buffers, ids, start,
                                          seg_lens, tables, k, v,
                                          logits_at=idx)
                last = _one_position(logits, idx)
                return _select(last, temperature, uniform), last, k2, v2

            return {"prefill": _prefill, "decode": _decode,
                    "chunked": _prefill_chunked, "verify": _chunked}

        self._make_fns = _make_fns
        fns = _make_fns(use_pallas)

        # the pools: the last two operands of each program
        donate_pf = (7, 8) if self._donate else ()
        donate_dc = (9, 10) if self._donate else ()
        donate_ck = (8, 9) if self._donate else ()
        donate_vf = (6, 7) if self._donate else ()
        self._prefill_jit = jax.jit(fns["prefill"],
                                    donate_argnums=donate_pf)
        self._decode_jit = jax.jit(fns["decode"],
                                   donate_argnums=donate_dc)
        self._chunked_jit = jax.jit(fns["chunked"],
                                    donate_argnums=donate_ck)
        self._verify_jit = jax.jit(fns["verify"],
                                   donate_argnums=donate_vf)
        # shadow-verification support (observability.numerics): oracle
        # jits re-trace the SAME closures with use_pallas=False and NO
        # donation — the oracle runs strictly before the real call so
        # the donated operands are still alive when it reads them.
        # Built lazily: zero cost until the first sampled shadow.
        self._oracle_fns = None
        self._oracle_jits: Dict[str, object] = {}
        self._div_jit = None
        # the one program beside the model's: a lane takes the token
        # the host names (0 and above), else keeps the one it carried
        def _lane_tokens(carried, fresh):
            return jnp.where(fresh >= 0, fresh, carried)

        self._lane_tokens_jit = jax.jit(_lane_tokens)

    def refresh_params(self):
        """Re-snapshot the model's current parameter arrays (they are
        call operands, not baked constants, so no recompile — the
        hybrid-parallel generate helper calls this per generate() so a
        training step between calls is picked up)."""
        from ...jit.functional import state_arrays
        self._params, self._buffers = state_arrays(self.model)
        if self.serving_mesh.live:
            self._params, self._buffers = self.serving_mesh.place_state(
                self._params, self._buffers, model=self.model)

    # ------------------------------------------------------ identity
    def fingerprint(self) -> str:
        """Stable identity of (model params/config/code, decode
        geometry) for persistent-cache keys and the warmup manifest."""
        if self._fp is None:
            from ...compile_cache import layer_fingerprint
            geom = {"max_batch": self.max_batch,
                    "page_size": self.page_size,
                    "pages_per_seq": self.pages_per_seq,
                    "max_positions": self.max_positions,
                    "donate": self._donate,
                    "use_pallas": self.use_pallas,
                    # "v" stands for what layer_fingerprint does not
                    # hash: the code under paddle_tpu/ops/ these
                    # programs are traced from. Bump it with any edit
                    # there that changes the lowered program, metadata
                    # included (v4: named scopes in paged and flash
                    # attention; v5: the decode kernel that loops over
                    # a lane's live pages, and prefill that keeps
                    # attention_bshd beside it; v6: the head on one
                    # position a row in prefill, counters beside the
                    # logits, windows and grouped heads in the ops;
                    # v7: the pools' heads folded into their lanes;
                    # v8: the programs choose the next token; v9: the
                    # decode step takes its tokens as it returns them,
                    # [max_batch] int32; v10: a share of the experts
                    # hands its products the capacity's rows; v11: the
                    # latent kernel's copies run on across lanes)
                    "kv_dtype": self.kv_dtype, "v": 11}
            # mesh axes + weight spec-tree hash join the geometry ONLY
            # when the mesh is live: an inert (None / 1-device) mesh
            # must reuse today's fingerprints byte-for-byte, and a mesh
            # or spec change must miss every cache keyed on this
            mesh_parts = self.serving_mesh.fingerprint_parts(self.model)
            if mesh_parts is not None:
                geom["serving_mesh"] = mesh_parts
            h = hashlib.sha256(layer_fingerprint(self.model).encode())
            h.update(json.dumps(geom, sort_keys=True).encode())
            self._fp = h.hexdigest()
        return self._fp

    # ------------------------------------------------------ dispatch
    @staticmethod
    def _sig_of(args) -> tuple:
        """Shape signature of the NON-weight operands (params/buffers
        are fixed for this decoder's lifetime — hashing their hundreds
        of leaves per decode step would be pure overhead)."""
        import jax
        return tuple(
            (tuple(int(d) for d in a.shape), str(np.dtype(a.dtype)))
            for a in jax.tree_util.tree_leaves(args[2:]))

    def _aot_exec(self, site: str, jitted, args):
        """Persistent-cache tier (mirrors Predictor._aot_serving_call):
        load-or-compile an AOT executable for this signature, memoized
        per (site, signature, flags generation); any failure degrades
        to the plain jitted dispatch."""
        from ...framework.flags import flag_value, flags_generation
        if not str(flag_value("FLAGS_compile_cache_dir") or ""):
            return None
        sig = (site, flags_generation()) + self._sig_of(args)
        if self.serving_mesh.live:
            # PR 10 pattern: spec-tree edits bump the generation, so a
            # re-annotated model can never hit a stale sharded AOT memo
            from ...distributed.shard import specs_generation
            sig = sig + ("specs_gen", specs_generation())
        memo = self._aot
        if sig in memo:
            fn = memo[sig]
            return fn if fn is not False else None
        fn = None
        try:
            import jax

            from ... import compile_cache as cc
            cache = cc.default_cache()
            if cache is not None:
                # under a live mesh the executable is compiled for the
                # operands' committed layouts (weights by spec tree,
                # pools by heads): lowered without them it would want
                # everything replicated and refuse the first call
                live = self.serving_mesh.live
                specs = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        tuple(a.shape), np.dtype(a.dtype),
                        sharding=a.sharding if live and isinstance(
                            a, jax.Array) else None), args)
                key, parts = cc.cache_key(
                    self.fingerprint(), list(specs),
                    mesh=self.serving_mesh.mesh_for_cache_key(),
                    extra={"site": site})
                fn, _hit = cache.get_or_compile(
                    key, lambda: jitted.lower(*specs).compile(),
                    site=site, meta=parts,
                    xstats_meta=self._xstats_meta(site, jitted, args))
        except Exception:  # noqa: BLE001 - AOT is an optimization
            fn = None      # tier; never let it break decode
        memo[sig] = fn if fn is not None else False
        return fn

    def _xstats_meta(self, site: str, jitted, args):
        """xstats registration payload for one decode entry point:
        decoder identity + a lower thunk over abstract operand specs
        (scrape-time only; params/buffers abstracted too)."""
        try:
            import jax

            from ...observability import xstats
            if not xstats.enabled():
                return None
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    tuple(a.shape), np.dtype(a.dtype)), args)
            return {"signature": self._sig_of(args),
                    "fingerprint": self.fingerprint(),
                    "lower_thunk": lambda: jitted.lower(*specs)}
        except Exception:  # noqa: BLE001 - observability is garnish
            return None

    def _xstats_note(self, site: str, sig: tuple, jitted, args,
                     used_aot: bool):
        """Per-dispatch note into the xstats registry (memoized by
        (site, signature) — steady-state cost is one dict hit plus a
        counter, on a path that just paid a device step)."""
        try:
            from ...observability import xstats
            if not xstats.enabled():
                return
            ent = self._xstats_entries.get(sig)
            if ent is None:
                xsig = sig[1:]   # drop the site prefix: site is the key
                if used_aot:
                    ent = xstats.register_executable(site, xsig)
                else:
                    meta = self._xstats_meta(site, jitted, args) or {}
                    ent = xstats.register_executable(
                        site, xsig,
                        fingerprint=meta.get("fingerprint"),
                        provenance={"cache": "off"},
                        lower_thunk=meta.get("lower_thunk"))
                if ent is None:
                    return
                self._xstats_entries[sig] = ent
            xstats.note_dispatch(ent)
        except Exception:  # noqa: BLE001 - never break a decode step
            pass

    # ------------------------------------------- numerics tripwires
    _ORACLE_KEYS = {"generate_decode": "decode",
                    "generate_chunked": "chunked",
                    "generate_verify": "verify"}

    def _oracle_jit(self, site: str):
        """Non-donating pure-JAX jit for a shadow-verified site, built
        from the same closure factory as the real entry points but
        with ``use_pallas=False`` (the reference implementation)."""
        fn = self._oracle_jits.get(site)
        if fn is None:
            import jax
            if self._oracle_fns is None:
                self._oracle_fns = self._make_fns(False)
            fn = jax.jit(self._oracle_fns[self._ORACLE_KEYS[site]])
            self._oracle_jits[site] = fn
        return fn

    def _divergence_fn(self):
        if self._div_jit is None:
            import jax
            import jax.numpy as jnp
            self._div_jit = jax.jit(
                lambda a, b: jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
        return self._div_jit

    def _numerics_shadow(self, site: str, args):
        """Sampled shadow re-execution through the pure-JAX oracle.
        MUST run before the real (possibly donating) call: the oracle
        jit never donates, and enqueue order guarantees it reads the
        pools before the real executable consumes them."""
        try:
            from ...observability import numerics
            if site not in numerics.SHADOW_SITES:
                return None
            if not numerics.sample_decision(numerics.shadow_rate()):
                return None
            return self._oracle_jit(site)(*args)
        except Exception:  # noqa: BLE001 - observability is garnish
            return None

    def _numerics_note(self, site: str, out, shadow_out):
        try:
            from ...observability import numerics
            kind = site[len("generate_"):]
            # a program that selects puts its tokens before its logits
            at = 0 if kind == "verify" else 1
            logits, k, v = out[at:at + 3]
            if shadow_out is not None:
                div = self._divergence_fn()(logits, shadow_out[at])
                numerics.note_shadow_divergence(
                    kind, self.kv_dtype or "f32", div)
            if numerics.sample_decision(numerics.tripwire_rate()):
                numerics.note_serving_logits(kind, logits)
                if self.kv_dtype == "int8":
                    numerics.note_int8_scales(kind, k, v)
        except Exception:  # noqa: BLE001 - never break a decode step
            pass

    def _dispatch(self, site: str, jitted, args) -> Tuple[object, bool]:
        """Returns ``(outputs, was_new_signature)``. The executable's
        call is the ``decoder::launch`` span; ``last_launch_s`` its
        length."""
        sig = (site,) + self._sig_of(args)
        fresh = sig not in self.compiled_signatures
        self.compiled_signatures.add(sig)
        shadow_out = self._numerics_shadow(site, args)
        aot = self._aot_exec(site, jitted, args)
        fn = aot or jitted
        span = RecordEvent("decoder::launch")
        with span:
            out = fn(*args)
        self.last_launch_s = span.elapsed_s
        self._xstats_note(site, sig, jitted, args, aot is not None)
        self._numerics_note(site, out, shadow_out)
        return out, fresh

    def _selection(self, rows: int, temperature, uniform) -> tuple:
        """The two per-row operands of a program that selects, float32
        ``[rows]``; None for either is every row greedy."""
        return tuple(
            np.zeros(rows, np.float32) if a is None
            else np.ascontiguousarray(a, np.float32)
            for a in (temperature, uniform))

    def _refuse_window(self, what: str):
        if self.has_state:
            raise NotImplementedError(
                f"{what} with {type(self.model).__name__}: its layers of "
                f"the 'state' kind keep the state after a sequence's "
                f"last token alone, so a window cannot start from a "
                f"cached prefix nor be rolled back")
        if self.has_latent:
            raise NotImplementedError(
                f"{what} with {type(self.model).__name__}: its latent "
                f"attention's pool serves prefill and decode, and no "
                f"window of several positions over a cached prefix is "
                f"built for it")

    def prefill(self, ids: np.ndarray, prompt_lens: np.ndarray,
                tables: np.ndarray, temperature, uniform, k, v):
        """ids [B, S] int64 (left-aligned, zero-padded); prompt_lens
        [B] int32 (0 = dead pad row); tables [B, P] int32; temperature
        and uniform [B] float32 (None: greedy). Returns ``(tokens [B]
        int32, last_logits [B, vocab], k', v', new_signature)``, the
        arrays on the device."""
        args = (self._params, self._buffers,
                np.ascontiguousarray(ids, np.int64),
                np.ascontiguousarray(prompt_lens, np.int32),
                np.ascontiguousarray(tables, np.int32),
                *self._selection(len(ids), temperature, uniform), k, v)
        (toks, last, k2, v2, aux), fresh = self._dispatch(
            "generate_prefill", self._prefill_jit, args)
        self.last_aux = aux
        return toks, last, k2, v2, fresh

    def prefill_chunked(self, ids: np.ndarray, start: np.ndarray,
                        seg_lens: np.ndarray, tables: np.ndarray,
                        temperature, uniform, k, v):
        """Suffix prefill after a prefix-cache hit. ids [B, S] int64
        (left-aligned suffix tokens); start [B] int32 per-row absolute
        offset (= matched prefix length, 0 = dead row); seg_lens [B]
        int32 real suffix lengths; tables [B, P] int32 (prefix pages
        first, then the row's private pages); temperature and uniform
        as ``prefill`` takes them. Returns ``(tokens [B] int32,
        last_logits [B, vocab], k', v', new_signature)``."""
        self._refuse_window("prefill_chunked")
        args = (self._params, self._buffers,
                np.ascontiguousarray(ids, np.int64),
                np.ascontiguousarray(start, np.int32),
                np.ascontiguousarray(seg_lens, np.int32),
                np.ascontiguousarray(tables, np.int32),
                *self._selection(len(ids), temperature, uniform), k, v)
        (toks, last, k2, v2), fresh = self._dispatch(
            "generate_chunked", self._chunked_jit, args)
        self.last_aux = {}
        return toks, last, k2, v2, fresh

    def verify(self, tokens: np.ndarray, start: np.ndarray,
               seg_lens: np.ndarray, tables: np.ndarray, k, v):
        """Speculative verify: one fixed-shape chunked forward over the
        [last_accepted, d_1..d_k] window per lane, returning ALL window
        logits ``[B, S, vocab]`` (S = spec_k + 1) so the host judges
        every proposal in one device step. Rejected positions' K/V
        writes land on the lane's already-reserved pages and are rolled
        back by context-length truncation, never by pool mutation."""
        self._refuse_window("verify")
        args = (self._params, self._buffers,
                np.ascontiguousarray(tokens, np.int64),
                np.ascontiguousarray(start, np.int32),
                np.ascontiguousarray(seg_lens, np.int32),
                np.ascontiguousarray(tables, np.int32), k, v)
        (logits, k2, v2), fresh = self._dispatch(
            "generate_verify", self._verify_jit, args)
        self.last_aux = {}
        return logits, k2, v2, fresh

    def lane_tokens(self, carried, fresh: np.ndarray):
        """``carried`` ``[B]`` int32 (a decode step's tokens, on the
        device, maybe of a step still running) with ``fresh`` ``[B]``
        written over it where ``fresh`` is 0 or more: the lanes whose
        token the host knows (a prefill chose it). One small program,
        nothing fetched; the result is a ``decode`` operand."""
        return self._lane_tokens_jit(
            carried, np.ascontiguousarray(fresh, np.int32))

    def decode(self, tokens, positions: np.ndarray,
               active: np.ndarray, ctx: np.ndarray,
               tables: np.ndarray, temperature, uniform, k, v):
        """One fixed-shape decode step. tokens [B] int32, on the host
        (any integer type: it is cast) or on the device (the tokens an
        earlier step returned, or ``lane_tokens`` of them: the same
        signature, and no host in between); positions [B]
        int32 (slot being written); active [B] bool; ctx [B] int32
        visible length INCLUDING this token; tables [B, P] int32;
        temperature and uniform [B] float32 (None: greedy; a dead
        lane's token means nothing). Returns ``(next_tokens [B] int32,
        logits [B, vocab], k', v', new_signature)``, the arrays on the
        device."""
        import jax
        if not (isinstance(tokens, jax.Array)
                and tokens.dtype == np.int32):
            tokens = np.ascontiguousarray(tokens, np.int32)
        args = (self._params, self._buffers, tokens,
                np.ascontiguousarray(positions, np.int32),
                np.ascontiguousarray(active, bool),
                np.ascontiguousarray(ctx, np.int32),
                np.ascontiguousarray(tables, np.int32),
                *self._selection(len(tokens), temperature, uniform), k, v)
        (toks, logits, k2, v2, aux), fresh = self._dispatch(
            "generate_decode", self._decode_jit, args)
        self.last_aux = aux
        return toks, logits, k2, v2, fresh
