"""shardcheck — compile-time sharding + per-chip memory regression gate.

Correctness-at-scale for the unified sharding API
(``paddle_tpu.distributed.shard``) must be checkable with NO TPU
attached: this tool AOT-compiles sharded train/predict steps against
abstract mesh topologies (the ``_ernie10b_plan`` trick — on a real
``jax.experimental.topologies`` TPU topology when one is requested and
available, else the local forced-CPU virtual devices), extracts the
per-chip memory plan and per-parameter shardings from the compiled
artifact, projects model-state bytes to the plan's TARGET chip count
from the spec tree, and gates everything against a committed baseline
JSON (pdlint/perfci style) — so every future sharding change is
validated at compile time in CI.

Usage:

    python tools/shardcheck.py                       # gate all plans
    python tools/shardcheck.py --plans ernie10b      # one plan
    python tools/shardcheck.py --json                # machine-readable
    python tools/shardcheck.py --write-baseline      # re-baseline
    python tools/shardcheck.py --tpu-topology v5e:8x8  # real XLA:TPU AOT

Exit codes: 0 = all gates pass against the baseline, 1 = regression,
2 = usage/internal error. The CI twin is tests/test_shardcheck.py
(fast plans only; the ERNIE-10B plan is the slow tier / this CLI).

Gate semantics per plan (tolerances live in the baseline file):

- the sharded step must COMPILE (XLA:TPU additionally enforces the
  15.75 GiB/chip HBM budget at compile time when on a TPU topology);
- measured per-chip argument bytes must stay within tolerance of the
  baseline (ZeRO/TP sharding actually took — a broken spec tree shows
  up as an 8-64x jump here);
- the spec-tree projection to the target topology (e.g. v5e-64) must
  stay within tolerance AND under the plan's budget;
- the sharded-bytes fraction must not drop;
- the spec-tree hash must match (an intentional sharding change is
  re-baselined with --write-baseline, after review).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

GIB = 1024 ** 3
SCHEMA = 1

DEFAULT_BASELINE = os.path.join(REPO_ROOT, "tests", "fixtures",
                                "shardcheck_baseline.json")


# ------------------------------------------------------------ topology
def tpu_topology_mesh(topology_name: str, axes: dict):
    """A mesh over a REAL XLA:TPU AOT topology (no chips attached),
    described in this process — the one that compiles against it. A
    topology that cannot be described raises what jax raises; None
    only when its device count is not the plan's, and the caller then
    labels the local mesh it took instead."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology_name)
    devs = np.array(topo.devices)
    names = list(axes.keys())
    degrees = [int(axes[n]) for n in names]
    if devs.size != int(np.prod(degrees)):
        return None
    return Mesh(devs.reshape(degrees), names)


def local_mesh(axes: dict):
    """Fallback mesh over the locally visible (virtual CPU) devices,
    scaling each axis down to what's available while keeping the axis
    NAMES stable so the spec tree is topology-independent."""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    names = list(axes.keys())
    degrees = []
    avail = len(devs)
    for n in names:
        d = int(axes[n])
        while d > 1 and (avail % d != 0 or d > avail):
            d //= 2
        degrees.append(max(d, 1))
        avail //= max(degrees[-1], 1)
    total = int(np.prod(degrees))
    return Mesh(np.asarray(devs[:total]).reshape(degrees), names)


# ---------------------------------------------------------------- plans
def _train_step_for(model, optimizer, loss_fn, amp_level=None):
    from paddle_tpu.jit import TrainStep
    return TrainStep(model, loss_fn, optimizer, amp_level=amp_level)


def _plan_ernie(cfg_factory, target_axes, budget_gib, seq, batch_per_chip,
                moment_dtype="bfloat16", amp_level="O2",
                serving_mp=None):
    """ZeRO-3 ERNIE plan through the unified API: LazyGuard abstract
    params (~0 bytes of host RAM), ``apply_sharding(zero='p_g_os')``
    instead of the manual ``group_sharded_parallel`` wiring, AMP O2 +
    bf16 moments (BASELINE config 5)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed import shard
    from paddle_tpu.models import ErnieForSequenceClassification

    def build(mesh):
        with paddle.LazyGuard():
            model = ErnieForSequenceClassification(cfg_factory())
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     moment_dtype=moment_dtype)
        specs = shard.apply_sharding(model, mesh=mesh, zero="p_g_os")
        step = _train_step_for(model, opt,
                               lambda o, y: F.cross_entropy(o, y),
                               amp_level=amp_level)
        n = mesh.devices.size
        bsz = batch_per_chip * n
        batch = (jax.ShapeDtypeStruct((bsz, seq), jnp.int64),
                 jax.ShapeDtypeStruct((bsz,), jnp.int64))

        def predict_lowered():
            from paddle_tpu.jit.functional import functional_call
            repl = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            p_sh = shard.param_shardings(mesh, model.named_parameters())
            params_abs = {
                name: jax.ShapeDtypeStruct(tuple(p.shape), p._data.dtype,
                                           sharding=p_sh[name])
                for name, p in model.named_parameters()}
            buffers_abs = {
                name: jax.ShapeDtypeStruct(tuple(b.shape), b._data.dtype,
                                           sharding=repl)
                for name, b in model.named_buffers() if b is not None}
            ids = jax.ShapeDtypeStruct(
                (bsz, seq), jnp.int64,
                sharding=jax.sharding.NamedSharding(
                    mesh, shard.batch_spec(mesh)))

            def fwd(params, buffers, x):
                return functional_call(model, params, buffers, x,
                                       training=False)

            return jax.jit(fwd).lower(params_abs, buffers_abs, ids)

        return dict(model=model, step=step, batch=batch,
                    predict_lowered=predict_lowered, specs=specs)

    serving = None
    if serving_mp:
        # encoder-only (no cached decode), so the serving rows are
        # analytic: weight bytes through the name rules + KV geometry
        # from the config (what an mp-replica serving this family's
        # decoder variant would hold per chip)
        cfg = cfg_factory()
        serving = dict(
            axes={"mp": int(serving_mp)},
            geom=dict(num_layers=int(cfg.num_layers),
                      num_heads=int(cfg.num_heads),
                      head_dim=int(cfg.hidden_size) // int(cfg.num_heads),
                      max_seq_len=int(cfg.max_position_embeddings)))

    return dict(build=build, target_axes=dict(target_axes),
                budget_gib=budget_gib,
                mesh_axes={k: v for k, v in target_axes.items()},
                serving=serving)


def plan_ernie10b():
    from paddle_tpu.models import ernie_3_0_10b
    return _plan_ernie(
        lambda: ernie_3_0_10b(hidden_dropout_prob=0.0,
                              attention_probs_dropout_prob=0.0,
                              recompute=True),
        target_axes={"sharding": 64},   # v5e-64
        budget_gib=15.75, seq=1024, batch_per_chip=1,
        serving_mp=8)                   # one v5e-8 serving replica


def plan_ernie_tiny():
    """Fast CI plan: same code path as ernie10b at toy scale (the
    tier-1 gate; exercises LazyGuard + ZeRO-3 + AOT on the 8-device
    virtual CPU mesh)."""
    from paddle_tpu.models.ernie import ernie_tiny
    return _plan_ernie(
        lambda: ernie_tiny(),
        target_axes={"sharding": 8},
        budget_gib=None, seq=32, batch_per_chip=1,
        serving_mp=4)


def plan_gpt_tiny_tp():
    """TP + dp plan over the rule-table conventions (no ZeRO): the
    multi-chip-serving direction — params shard over 'mp' by the
    embedding/attention/MLP rules."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.distributed import shard
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt_tiny)

    def build(mesh):
        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny(use_flash_attention=False))
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        specs = shard.apply_sharding(model, mesh=mesh)
        crit = GPTPretrainingCriterion()
        step = _train_step_for(model, opt, lambda o, y: crit(o, y))
        n_dp = mesh.shape.get("dp", 1)
        batch = (jax.ShapeDtypeStruct((2 * max(n_dp, 1), 32), jnp.int64),
                 jax.ShapeDtypeStruct((2 * max(n_dp, 1), 32), jnp.int64))
        return dict(model=model, step=step, batch=batch,
                    predict_lowered=None, specs=specs)

    # gpt_tiny has the cached-decode contract, so its serving section
    # ALSO AOT-compiles the sharded prefill+decode executables (the
    # tier-1 serving gate; ernie plans only get the analytic rows)
    return dict(build=build, target_axes={"dp": 2, "mp": 4},
                budget_gib=None, mesh_axes={"dp": 2, "mp": 4},
                serving=dict(axes={"mp": 4}))


PLANS = {
    "ernie10b": plan_ernie10b,
    "ernie_tiny_zero3": plan_ernie_tiny,
    "gpt_tiny_tp": plan_gpt_tiny_tp,
}

# the fast subset the test suite gates on every run
FAST_PLANS = ("ernie_tiny_zero3", "gpt_tiny_tp")


# ------------------------------------------------------------ execution
def _kv_projection(model, page_size: int = 16, max_batch: int = 8):
    """Serving KV-pool byte projection per pool dtype (the quantized-KV
    sizing story, gated like the sharding bytes): for each supported
    ``FLAGS_decode_kv_dtype`` this projects the engine's resident pool
    bytes — including the capacity factor the engine actually grants
    (sub-f32 dtypes get 2x pages, i.e. ~2x resident sequences) — so a
    regression in the quantized layout (a scale plane growing, the
    sizing rule regressing) trips the baseline gate.

    None for models without the cached-decode contract."""
    import numpy as np

    from paddle_tpu.ops.paged_attention import kv_pool_bytes
    from paddle_tpu.serving.generation.model_fns import \
        supports_cached_decode

    if not supports_cached_decode(model):
        return None
    spec = model.kv_cache_spec()
    nh, hd = spec["num_heads"], spec["head_dim"]
    layers = spec["num_layers"]
    pages_per_seq = -(-spec["max_seq_len"] // page_size)
    f32_tok = kv_pool_bytes(1, 1, nh, hd, None)
    dtypes = {}
    for dt in ("float32", "bfloat16", "int8"):
        tok = kv_pool_bytes(1, 1, nh, hd, dt)
        factor = max(1, min(2, f32_tok // max(tok, 1)))
        num_pages = 1 + max_batch * pages_per_seq * factor
        pool = layers * 2 * kv_pool_bytes(num_pages, page_size,
                                          nh, hd, dt)
        dtypes[dt] = {"token_bytes": int(tok),
                      "capacity_factor": int(factor),
                      "num_pages": int(num_pages),
                      "pool_bytes": int(pool)}
    ratio = dtypes["float32"]["token_bytes"] / \
        dtypes["int8"]["token_bytes"]
    return {"page_size": page_size, "max_batch": max_batch,
            "pages_per_seq": int(pages_per_seq),
            "head_dim": int(hd),
            "dtypes": dtypes,
            # per-token shrink 4/(1+4/D): 3.76x at D=64
            "int8_bytes_ratio": round(float(ratio), 4)}


def _serving_aot(model, serving_axes, page_size: int, max_batch: int):
    """AOT-compile the SHARDED prefill + decode executables exactly as
    the serving engine builds them — a ``CachedDecoder`` bound to a
    live ``{'mp': N}`` ``ServingMesh`` (serving/mesh.py), pools placed
    heads-sharded, weights placed by the spec tree — and return their
    per-chip memory plans. A spec tree that stops partitioning or a
    decode graph that stops compiling under a live mesh fails HERE at
    compile time, with no TPU attached. Uses the pure-JAX kernel path
    (the shadow-verification oracle): that is the canonical GSPMD
    partitioning the Pallas shard_map dispatch must agree with."""
    import jax.numpy as jnp

    from paddle_tpu.serving.generation.model_fns import CachedDecoder
    from paddle_tpu.serving.mesh import ServingMesh

    mesh = local_mesh(dict(serving_axes))
    smesh = ServingMesh(mesh)
    if not smesh.live:
        return None      # axes collapsed to 1 device — nothing to gate
    pages_per_seq = 2
    dec = CachedDecoder(model, max_batch=max_batch, page_size=page_size,
                        pages_per_seq=pages_per_seq, donate=False,
                        use_pallas=False, mesh=smesh)
    k, v = model.init_kv_pools(1 + max_batch * pages_per_seq, page_size)
    k, v = smesh.place_pools(k, v)
    b, s = max_batch, page_size
    ids = jnp.zeros((b, s), dtype=jnp.int32)
    plens = jnp.full((b,), s, dtype=jnp.int32)
    tables = jnp.zeros((b, pages_per_seq), dtype=jnp.int32)
    greedy = (jnp.zeros((b,), dtype=jnp.float32),) * 2
    prefill = dec._prefill_jit.lower(
        dec._params, dec._buffers, ids, plens, tables, *greedy,
        k, v).compile()
    tokens = jnp.zeros((b,), dtype=jnp.int32)
    positions = jnp.full((b,), s, dtype=jnp.int32)
    active = jnp.ones((b,), dtype=bool)
    ctx = jnp.full((b,), s + 1, dtype=jnp.int32)
    decode = dec._decode_jit.lower(
        dec._params, dec._buffers, tokens, positions, active, ctx,
        tables, *greedy, k, v).compile()
    out = {}
    for site, comp in (("prefill", prefill), ("decode", decode)):
        ma = comp.memory_analysis()
        out[site] = {"args_bytes": int(ma.argument_size_in_bytes),
                     "temp_bytes": int(ma.temp_size_in_bytes)}
    out["n_chips_compiled"] = int(mesh.devices.size)
    out["mesh_axes"] = {a: int(d) for a, d in mesh.shape.items()}
    return out


def _serving_record(model, serving_axes: dict, geom=None,
                    page_size: int = 16, max_batch: int = 8):
    """Tensor-parallel SERVING projection at the replica's mesh degree
    (serving/mesh.py: fleet replica = mesh): per-chip weight bytes
    through the serving rule tables — the same name-based specs
    ``Predictor.attach_serving_mesh`` places by, NOT the training
    plan's ZeRO overrides — plus per-chip heads-sharded KV-pool bytes
    per supported ``FLAGS_decode_kv_dtype`` (the per-dtype projection
    above composed with the ``heads/mp`` split; host-side page
    bookkeeping is layout-agnostic, only device bytes divide). Models
    with the cached-decode contract additionally AOT-compile the
    sharded prefill + decode entry points (``_serving_aot``).

    ``geom`` supplies {num_layers, num_heads, head_dim, max_seq_len}
    for encoder-only models (ernie10b) that have no
    ``kv_cache_spec()``; their serving rows are analytic."""
    from paddle_tpu.distributed import shard
    from paddle_tpu.ops.paged_attention import kv_pool_bytes
    from paddle_tpu.serving.generation.model_fns import \
        supports_cached_decode

    mp = int(serving_axes.get("mp", 1))
    if geom is None:
        spec = model.kv_cache_spec()
        geom = {key: int(spec[key]) for key in
                ("num_layers", "num_heads", "head_dim", "max_seq_len")}
    nh, hd = geom["num_heads"], geom["head_dim"]
    heads_ok = mp <= 1 or nh % mp == 0

    rules = shard.default_rules()
    named = dict(model.named_parameters())
    specs = {n: rules.spec_for(n, tuple(p.shape))
             for n, p in named.items()}
    proj = shard.projected_bytes_per_chip(named, specs, serving_axes)

    pages_per_seq = -(-geom["max_seq_len"] // page_size)
    f32_tok = kv_pool_bytes(1, 1, nh, hd, None)
    per_dtype = {}
    for dt in ("float32", "bfloat16", "int8"):
        tok = kv_pool_bytes(1, 1, nh, hd, dt)
        factor = max(1, min(2, f32_tok // max(tok, 1)))
        num_pages = 1 + max_batch * pages_per_seq * factor
        pool = geom["num_layers"] * 2 * kv_pool_bytes(
            num_pages, page_size, nh, hd, dt)
        per_dtype[dt] = {
            "pool_bytes": int(pool),
            "per_chip_pool_bytes":
                int(pool // mp) if heads_ok and mp > 1 else int(pool),
        }
    rec = {
        "serving_axes": dict(serving_axes),
        "heads_shardable": bool(heads_ok),
        "num_heads": int(nh),
        "page_size": int(page_size),
        "max_batch": int(max_batch),
        "weights_per_chip_bytes": int(proj["total_bytes"]),
        "weights_spec_hash": shard.spec_tree_hash(specs),
        "kv_per_chip": per_dtype,
        "aot": None,
    }
    if supports_cached_decode(model) and heads_ok and mp > 1:
        rec["aot"] = _serving_aot(model, serving_axes, page_size,
                                  max_batch)
    return rec


def _mesh_kind(mesh) -> str:
    kinds = sorted({getattr(d, "device_kind", str(d))
                    for d in mesh.devices.flat})
    return f"{mesh.devices.size}x {'/'.join(kinds)}"


def _sharding_counts(specs, named_params, mesh_axes):
    import numpy as np
    sharded = repl = 0
    sharded_b = total_b = 0
    for name, p in named_params.items():
        spec = specs.get(name, ())
        shape = tuple(p.shape)
        n_elem = int(np.prod(shape)) if shape else 1
        dt = getattr(getattr(p, "_data", None), "dtype", "float32")
        nbytes = n_elem * np.dtype(str(dt)).itemsize
        total_b += nbytes
        if any(a is not None for a in spec):
            sharded += 1
            sharded_b += nbytes
        else:
            repl += 1
    return {"sharded_params": sharded, "replicated_params": repl,
            "sharded_fraction_bytes":
                round(sharded_b / total_b, 6) if total_b else 0.0}


def run_plan(name: str, tpu_topology: str = "") -> dict:
    """Build, AOT-compile and measure one plan; returns the record the
    baseline gate consumes."""
    import numpy as np

    from paddle_tpu.distributed import shard
    from paddle_tpu.distributed.mesh_utils import set_global_mesh

    plan = PLANS[name]()
    mesh = None
    topo_label = ""
    if tpu_topology:
        mesh = tpu_topology_mesh(tpu_topology, plan["mesh_axes"])
        topo_label = f"{tpu_topology} (AOT topology)"
    on_tpu_topo = mesh is not None
    if mesh is None:
        mesh = local_mesh(plan["mesh_axes"])
        topo_label = f"{_mesh_kind(mesh)} (local fallback)"
    set_global_mesh(mesh)
    try:
        built = plan["build"](mesh)
        step, model = built["step"], built["model"]
        compiled = step.aot_lower(mesh, *built["batch"])
        ma = compiled.memory_analysis()
        per_chip = {
            "args_bytes": int(ma.argument_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "out_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        predict = None
        if built.get("predict_lowered") is not None:
            pcomp = built["predict_lowered"]().compile()
            pma = pcomp.memory_analysis()
            predict = {"args_bytes": int(pma.argument_size_in_bytes),
                       "temp_bytes": int(pma.temp_size_in_bytes)}
        specs = built["specs"]
        named = dict(model.named_parameters())
        opt = step.optimizer
        opt_bytes = 0
        for an in opt._accum_names:
            # accumulator bytes per element (moments may be bf16)
            shape, dtype = opt._accum_spec(an, next(iter(named.values())))
            opt_bytes += np.dtype(str(dtype)).itemsize \
                if len(shape) else 0
        os_specs = {n: (getattr(p, "opt_state_spec", None) or
                        specs.get(n, ())) for n, p in named.items()}
        proj = shard.projected_bytes_per_chip(
            named, specs, plan["target_axes"],
            opt_bytes_per_param=opt_bytes, opt_specs=os_specs)
        n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
        rec = {
            "schema": SCHEMA,
            "plan": name,
            "topology": topo_label,
            "on_tpu_topology": bool(on_tpu_topo),
            "n_chips_compiled": int(mesh.devices.size),
            "target_axes": plan["target_axes"],
            "budget_gib": plan["budget_gib"],
            "n_params": int(n_params),
            "per_chip": per_chip,
            "predict_per_chip": predict,
            "projected_per_chip": {
                "target_chips": int(np.prod(list(
                    plan["target_axes"].values()))),
                **proj,
                "model_state_gib": round(proj["total_bytes"] / GIB, 4),
            },
            "spec_tree_hash": shard.spec_tree_hash(
                shard.model_spec_tree(model)),
            "kv_projection": _kv_projection(model),
        }
        rec.update(_sharding_counts(specs, named, plan["target_axes"]))
        serving = plan.get("serving")
        if serving:
            # the serving path threads its mesh EXPLICITLY (engine
            # worker threads never see the thread-local global mesh) —
            # clear the training mesh first so the compile below sees
            # exactly what the engine sees
            set_global_mesh(None)
            rec["serving"] = _serving_record(model, serving["axes"],
                                             geom=serving.get("geom"))
        return rec
    finally:
        set_global_mesh(None)


# ----------------------------------------------------------------- gate
def gate_record(rec: dict, base: dict) -> list:
    """Failures of one plan record against its baseline entry. Empty
    list = pass."""
    fails = []
    tol = float(base.get("tolerance", 0.10))
    budget = rec.get("budget_gib")

    def _within(cur, ref, what):
        if ref and abs(cur - ref) > abs(ref) * tol:
            fails.append(f"{what}: {cur} vs baseline {ref} "
                         f"(>{tol:.0%} drift)")

    _within(rec["per_chip"]["args_bytes"],
            base["per_chip"]["args_bytes"], "per-chip argument bytes")
    _within(rec["projected_per_chip"]["total_bytes"],
            base["projected_per_chip"]["total_bytes"],
            "projected per-chip model-state bytes")
    if budget is not None and \
            rec["projected_per_chip"]["model_state_gib"] > budget:
        fails.append(
            f"projected model state "
            f"{rec['projected_per_chip']['model_state_gib']} GiB "
            f"exceeds the {budget} GiB/chip budget")
    if rec["sharded_fraction_bytes"] < \
            base["sharded_fraction_bytes"] - 0.01:
        fails.append(
            f"sharded-bytes fraction dropped: "
            f"{rec['sharded_fraction_bytes']} vs baseline "
            f"{base['sharded_fraction_bytes']}")
    if rec["spec_tree_hash"] != base["spec_tree_hash"]:
        fails.append(
            f"spec tree changed (hash {rec['spec_tree_hash'][:12]} vs "
            f"baseline {base['spec_tree_hash'][:12]}) — review the "
            f"sharding change, then --write-baseline")
    kv = rec.get("kv_projection")
    if kv is not None and base.get("kv_projection") is not None:
        bkv = base["kv_projection"]
        i8, f32 = kv["dtypes"]["int8"], kv["dtypes"]["float32"]
        _within(i8["pool_bytes"], bkv["dtypes"]["int8"]["pool_bytes"],
                "projected int8 KV pool bytes")
        # the quantized-KV contract: ~2x resident sequences that still
        # fit UNDER the f32 budget (the scale planes are the only
        # overhead, per-token shrink 4/(1+4/head_dim))
        if i8["capacity_factor"] < 2:
            fails.append(
                f"int8 capacity factor {i8['capacity_factor']} < 2 — "
                f"quantized pools no longer buy the ~2x headroom")
        if i8["pool_bytes"] > f32["pool_bytes"]:
            fails.append(
                f"int8 pool at 2x pages ({i8['pool_bytes']} B) "
                f"exceeds the f32 pool at 1x ({f32['pool_bytes']} B)")
        if kv["int8_bytes_ratio"] < bkv["int8_bytes_ratio"] - 0.01:
            fails.append(
                f"int8 per-token shrink regressed: "
                f"{kv['int8_bytes_ratio']}x vs baseline "
                f"{bkv['int8_bytes_ratio']}x")
    srv, bsrv = rec.get("serving"), base.get("serving")
    if srv is not None and bsrv is not None:
        _within(srv["weights_per_chip_bytes"],
                bsrv["weights_per_chip_bytes"],
                "serving per-chip weight bytes")
        for dt in ("float32", "int8"):
            _within(srv["kv_per_chip"][dt]["per_chip_pool_bytes"],
                    bsrv["kv_per_chip"][dt]["per_chip_pool_bytes"],
                    f"serving per-chip {dt} KV pool bytes")
        if bsrv.get("heads_shardable") and not srv.get("heads_shardable"):
            fails.append(
                f"serving heads axis no longer shardable: "
                f"{srv['num_heads']} heads do not divide "
                f"mp={srv['serving_axes'].get('mp')}")
        if srv["weights_spec_hash"] != bsrv["weights_spec_hash"]:
            fails.append(
                f"serving weight spec tree changed (hash "
                f"{srv['weights_spec_hash'][:12]} vs baseline "
                f"{bsrv['weights_spec_hash'][:12]}) — review the "
                f"rule-table change, then --write-baseline")
        if bsrv.get("aot") is not None:
            if srv.get("aot") is None:
                fails.append(
                    "sharded serving prefill+decode no longer "
                    "AOT-compile (baseline has an aot record)")
            else:
                for site in ("prefill", "decode"):
                    _within(srv["aot"][site]["args_bytes"],
                            bsrv["aot"][site]["args_bytes"],
                            f"sharded serving {site} per-chip "
                            f"argument bytes")
    return fails


def load_baseline(path: str) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        return json.load(f).get("plans", {})


def write_baseline(path: str, records: dict, tolerance: float = 0.10):
    # merge: re-baselining a SUBSET (--plans) must not drop the other
    # plans' committed entries
    plans = dict(load_baseline(path))
    for name, rec in records.items():
        entry = dict(rec)
        entry["tolerance"] = tolerance
        plans[name] = entry
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": SCHEMA, "tool": "shardcheck",
                   "plans": plans}, f, indent=1, sort_keys=True)
        f.write("\n")


# ------------------------------------------------------------------ cli
def build_parser():
    p = argparse.ArgumentParser(prog="shardcheck", description=__doc__,
                                formatter_class=argparse.
                                RawDescriptionHelpFormatter)
    p.add_argument("--plans", default=None,
                   help=f"comma-separated subset of {sorted(PLANS)} "
                        f"(default: all)")
    p.add_argument("--json", action="store_true", dest="as_json")
    p.add_argument("--baseline", default=DEFAULT_BASELINE)
    p.add_argument("--write-baseline", action="store_true")
    p.add_argument("--tolerance", type=float, default=0.10,
                   help="relative drift allowed on byte gates when "
                        "(re)writing the baseline")
    p.add_argument("--tpu-topology", default="",
                   help="try a real XLA:TPU AOT topology (e.g. "
                        "v5e:8x8); probed in a subprocess with a "
                        "timeout, falls back to local devices")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    names = list(PLANS)
    if args.plans:
        names = [n.strip() for n in args.plans.split(",") if n.strip()]
        unknown = set(names) - set(PLANS)
        if unknown:
            print(f"shardcheck: unknown plan(s) {sorted(unknown)} "
                  f"(have: {sorted(PLANS)})", file=sys.stderr)
            return 2

    records, failures = {}, {}
    for name in names:
        try:
            records[name] = run_plan(name, tpu_topology=args.tpu_topology)
        except Exception as e:  # noqa: BLE001 - a plan that cannot even
            failures[name] = [f"plan failed to compile: "  # compile IS
                              f"{type(e).__name__}: {e}"]  # the regression
    if args.write_baseline:
        if failures:
            for name, fs in failures.items():
                for f_ in fs:
                    print(f"shardcheck[{name}]: {f_}", file=sys.stderr)
            return 2
        write_baseline(args.baseline, records, args.tolerance)
        print(f"shardcheck: wrote baseline for {sorted(records)} to "
              f"{os.path.relpath(args.baseline, REPO_ROOT)}")
        return 0

    baseline = load_baseline(args.baseline)
    for name, rec in records.items():
        base = baseline.get(name)
        if base is None:
            failures.setdefault(name, []).append(
                "no baseline entry — run --write-baseline")
            continue
        fails = gate_record(rec, base)
        if fails:
            failures[name] = failures.get(name, []) + fails

    if args.as_json:
        print(json.dumps({"version": SCHEMA, "records": records,
                          "failures": failures}, indent=1,
                         sort_keys=True, default=repr))
        return 1 if failures else 0
    for name, rec in records.items():
        proj = rec["projected_per_chip"]
        print(f"shardcheck[{name}]: {rec['topology']}, "
              f"{rec['n_chips_compiled']} chips compiled, "
              f"args {rec['per_chip']['args_bytes'] / GIB:.3f} GiB/chip, "
              f"projected@{proj['target_chips']} "
              f"{proj['model_state_gib']:.3f} GiB model state"
              + (f" (budget {rec['budget_gib']} GiB)"
                 if rec["budget_gib"] else "")
              + f", specs {rec['spec_tree_hash'][:12]}")
        srv = rec.get("serving")
        if srv:
            i8 = srv["kv_per_chip"]["int8"]["per_chip_pool_bytes"]
            print(f"shardcheck[{name}]: serving "
                  f"mp={srv['serving_axes'].get('mp')}: weights "
                  f"{srv['weights_per_chip_bytes'] / GIB:.4f} GiB/chip, "
                  f"int8 KV {i8 / GIB:.4f} GiB/chip"
                  + (", sharded prefill+decode compiled"
                     if srv.get("aot") else ""))
    for name, fs in sorted(failures.items()):
        for f_ in fs:
            print(f"shardcheck[{name}]: FAIL: {f_}", file=sys.stderr)
    if not failures:
        print(f"shardcheck: {len(records)} plan(s) clean against "
              f"{os.path.relpath(args.baseline, REPO_ROOT)}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
