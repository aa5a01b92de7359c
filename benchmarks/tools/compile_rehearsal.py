#!/usr/bin/env python3
"""Compile a serve configuration's decode step and every prefill program
its file warms for a *described* v5e (no chip needed) and plan its KV pool from the
compiler's memory analysis: ``chip_smoke.plan_kv_pool``'s method (PR
21) over this configuration's own executables.

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_rehearsal.py \
        benchmarks/configs/gpt2-medium.json [--max-batch N]

Prints one row per program (arguments, temporaries, outputs, aliased
bytes) and the pages that fit: limit - weights - margin - the largest
program's (temp + out - alias), over one page's bytes. A compile that
passes is not a chip run; nothing here is a time.
"""
import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = 1024 ** 3
LIMIT = int(15.75 * GIB)       # bytes_limit a v5e reports (PR 21)
MARGIN = LIMIT // 32           # fragmentation and the parity forward


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--max-batch", type=int, default=0)
    ap.add_argument("--pages", type=int, default=0,
                    help="pool size to compile at (default: the file's)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import common
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving.generation.model_fns import CachedDecoder

    config = common.load_json(args.config)
    serve = config["serve"]
    mb = args.max_batch or int(serve["max_batch"])
    pages = args.pages or int(serve["num_pages"])
    cfg = common.build_model_config(config["model"],
                                    serve.get("model_kwargs"))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    model = GPTForCausalLM(cfg)
    model.eval()
    width = -(-int(serve["max_seq_len"]) // int(serve["page_size"]))
    dec = CachedDecoder(model, max_batch=mb,
                        page_size=int(serve["page_size"]),
                        pages_per_seq=width, donate=True,
                        max_positions=int(serve["max_seq_len"]),
                        use_pallas=False, kv_dtype="")
    params, buffers = state_arrays(model)
    as_sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: sds(a.shape, a.dtype), t)
    p, b = as_sds(params), as_sds(buffers)
    weights = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                  for a in params.values())
    nh, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    pool = [sds((pages, serve["page_size"], nh, hd), jnp.float32)
            for _ in range(cfg.num_layers)]
    page_bytes = 2 * cfg.num_layers * serve["page_size"] * nh * hd * 4

    rows = []

    def compile_one(label, jitted, *feeds):
        import time
        t0 = time.time()
        try:
            ana = jitted.lower(p, b, *feeds, pool, pool).compile(
            ).memory_analysis()
        except Exception as e:  # noqa: BLE001 - the compiler's refusal is
            print(f"{label:>24}: REFUSED: "      # the finding
                  f"{str(e).splitlines()[0][:300]}", flush=True)
            return
        rows.append((label, ana.argument_size_in_bytes,
                     ana.temp_size_in_bytes, ana.output_size_in_bytes,
                     ana.alias_size_in_bytes))
        print(f"{label:>24}: args {rows[-1][1] / GIB:6.2f} GiB  temp "
              f"{rows[-1][2] / GIB:6.3f} GiB  out {rows[-1][3] / GIB:6.2f}"
              f" GiB  alias {rows[-1][4] / GIB:6.2f} GiB  "
              f"({time.time() - t0:.0f} s to compile)", flush=True)

    compile_one(f"decode[{mb}]", dec._decode_jit,
                sds((mb,), jnp.int64), sds((mb,), jnp.int32),
                sds((mb,), jnp.bool_), sds((mb,), jnp.int32),
                sds((mb, width), jnp.int32))
    # every program the file warms: what a program needs is not
    # monotone in its shape (the scheduler keeps other things alive at
    # 512 positions than at 768), so the largest shape is not enough
    for seq in sorted(serve["warm"]["prefill_seq"], reverse=True):
        for rows_n in sorted((r for r in serve["warm"]["prefill_rows"]
                              if r <= mb), reverse=True):
            compile_one(f"prefill[{rows_n},{seq}]", dec._prefill_jit,
                        sds((rows_n, seq), jnp.int64),
                        sds((rows_n,), jnp.int32),
                        sds((rows_n, width), jnp.int32))
    extra = max(t + o - a for _, _, t, o, a in rows)
    free = LIMIT - weights - MARGIN
    fit = int((free - extra) // page_bytes)
    print(f"limit {LIMIT / GIB:.2f} GiB - weights {weights / GIB:.2f} GiB "
          f"- margin {MARGIN / GIB:.2f} GiB = {free / GIB:.2f} GiB; the "
          f"largest program needs {extra / GIB:.2f} GiB beside its "
          f"arguments; a page is {page_bytes / 2**20:.2f} MiB: "
          f"{fit} pages fit (compiled at {pages})")


if __name__ == "__main__":
    main()
