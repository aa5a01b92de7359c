#!/usr/bin/env python3
"""Compile a serve configuration's decode step and every prefill program
its file warms for a *described* v5e (no chip needed) and plan its KV pool from the
compiler's memory analysis: ``chip_smoke.plan_kv_pool``'s method (PR
21) over this configuration's own executables.

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_rehearsal.py \
        benchmarks/configs/gpt2-medium.json [--max-batch N]

It plans the programs the cell runs: the class the file names
(``model.class``), in the dtype it is served in
(``serve.weights_dtype``), over the pool arrays the model's own
``init_kv_pools`` makes, attended by whoever the program picks on a TPU
(``framework.place.on_tpu`` is steered from here, as
``tests/test_chip_compile.py`` steers it: this process sees a CPU).

Prints one row per program (arguments, temporaries, outputs, aliased
bytes) and the pages that fit: limit - weights - margin - the largest
program's (temp + out - alias), over one page's bytes. A compile that
passes is not a chip run; nothing here is a time.
"""
import argparse
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

GIB = 1024 ** 3
LIMIT = int(15.75 * GIB)       # bytes_limit a v5e reports (PR 21)
MARGIN = LIMIT // 32           # fragmentation and the parity forward


def nbytes(tree) -> int:
    import jax
    import numpy as np
    return sum(int(np.prod(a.shape)) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--max-batch", type=int, default=0)
    ap.add_argument("--pages", type=int, default=0,
                    help="pool size to compile at (default: the file's)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks import common
    from paddle_tpu.framework import place
    from paddle_tpu.jit.functional import state_arrays
    from paddle_tpu.serving.generation.model_fns import CachedDecoder

    config = common.load_json(args.config)
    common.check_config_keys(config, args.config)
    serve = config["serve"]
    mb = args.max_batch or int(serve["max_batch"])
    pages = args.pages or int(serve["num_pages"])
    page_size = int(serve["page_size"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    def as_sds(tree):
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype), tree)

    cfg, model = common.build_model(config, "serve")
    model.to(dtype=serve["weights_dtype"])
    model.eval()
    width = -(-int(serve["max_seq_len"]) // page_size)
    with mock.patch.object(place, "on_tpu", lambda: True):
        dec = CachedDecoder(model, max_batch=mb, page_size=page_size,
                            pages_per_seq=width, donate=True,
                            max_positions=int(serve["max_seq_len"]),
                            kv_dtype="")
        print(f"{type(model).__name__} in {serve['weights_dtype']}; decode "
              f"attention: {'the fused paged kernels' if dec.use_pallas else 'the pure-JAX body'}",
              flush=True)
        params, buffers = state_arrays(model)
        p, b = as_sds(params), as_sds(buffers)
        weights = nbytes(params)
        # the pool as the model itself lays it out, never allocated
        pools = as_sds(jax.eval_shape(
            lambda: model.init_kv_pools(pages, page_size)))
        page_bytes = nbytes(pools) // pages

        rows = []

        def compile_one(label, jitted, *feeds):
            t0 = time.time()
            try:
                compiled = jitted.lower(p, b, *feeds, *pools).compile()
            except Exception as e:  # noqa: BLE001 - the compiler's
                print(f"{label:>24}: REFUSED: "   # refusal is the finding
                      f"{str(e).splitlines()[0][:300]}", flush=True)
                return
            ana = compiled.memory_analysis()
            rows.append((label, ana.argument_size_in_bytes,
                         ana.temp_size_in_bytes, ana.output_size_in_bytes,
                         ana.alias_size_in_bytes))
            kernel = "kernel" if "tpu_custom_call" in compiled.as_text() \
                else "no kernel"
            print(f"{label:>24}: args {rows[-1][1] / GIB:6.2f} GiB  temp "
                  f"{rows[-1][2] / GIB:6.3f} GiB  out "
                  f"{rows[-1][3] / GIB:6.2f} GiB  alias "
                  f"{rows[-1][4] / GIB:6.2f} GiB  {kernel}  "
                  f"({time.time() - t0:.0f} s to compile)", flush=True)

        compile_one(f"decode[{mb}]", dec._decode_jit,
                    sds((mb,), jnp.int64), sds((mb,), jnp.int32),
                    sds((mb,), jnp.bool_), sds((mb,), jnp.int32),
                    sds((mb, width), jnp.int32))
        # every program the file warms: what a program needs is not
        # monotone in its shape (the scheduler keeps other things alive
        # at 512 positions than at 768), so the largest is not enough
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
