#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

finds the cell in ``BENCHMARK.json``, its configuration file, its
traffic file, its configuration's reference and the reader of each
metric by name, runs the cell with the runner its traffic ``kind``
names, and prints one JSON object as the last line of its output:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``compared``: each number that
decided ``correct`` beside its limit (also the last lines of standard
error).

With ``--trace 0`` the metrics are the cell's end-to-end metrics and
the profiler is off; with ``--trace 1`` they are its per-layer metrics
and a few steady seconds in the middle of the window are traced.

It measures on a TPU and nowhere else. ``--rehearse`` is the CPU
rehearsal the tests use (tiny configurations, ``--manifest`` pointing
at a manifest of their own): the device is then named for what it is
and no metric that comes from a device trace is printed.
"""
import time

T_START = time.perf_counter()      # set-up counts from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RUNNERS = {"train-steps": "train_runner", "closed": "serve_runner",
           "open": "serve_runner"}


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def find_file(manifest: dict, sub: str, name: str, suffixes) -> str:
    """``<path>/<sub>/<name><suffix>`` in the manifest's ``paths``,
    then beside this file."""
    roots = [os.path.join(ROOT, p) for p in manifest["paths"]] + [HERE]
    for root in roots:
        for suffix in suffixes:
            path = os.path.join(root, sub, name + suffix)
            if os.path.exists(path):
                return path
    raise FileNotFoundError(
        f"no {sub}/{name}{'|'.join(suffixes)} under {manifest['paths']}")


def load_reader(manifest: dict, name: str):
    from benchmarks import common
    return common.load_module(
        find_file(manifest, "metrics", name, (".py",)),
        "benchmarks.metrics." + name.replace(".", "_").replace("-", "_"))


def metrics_of(manifest: dict, cell: str, group: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: no TPU needed, no device metric")
    ap.add_argument("--control", action="store_true",
                    help="a serve cell's control beside the program's "
                    "reading: the reference one precision step down, put "
                    "in the program's place (never in a measured run)")
    args = ap.parse_args(argv)

    from benchmarks import common, xplane
    manifest = common.load_json(args.manifest)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        sys.exit(f"no workload {args.workload!r} in {args.manifest} "
                 f"(has: {sorted(cells)})")
    cell = cells[args.workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    config = common.load_json(os.path.join(ROOT, config_entry["file"]))
    common.check_config_keys(config, config_entry["file"])
    reference_path = find_file(manifest, "reference", config["reference"],
                               (".py",))     # an unknown name fails here
    traffic = common.load_json(find_file(
        manifest, "traffic", cell["traffic"], (".json",)))
    if args.control and RUNNERS[traffic["kind"]] != "serve_runner":
        sys.exit(f"--control reads a serve cell's parity; {cell['name']} "
                 f"is of kind {traffic['kind']!r} (PERF.md section 7)")

    import paddle_tpu  # noqa: F401 - a checkout without the program fails here
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    dev = jax.devices()[0]       # a TPU that cannot start raises here
    if not args.rehearse:
        if dev.platform != "tpu":
            sys.exit(f"the benchmark measures on a TPU and jax found "
                     f"{dev.platform!r} ({dev.device_kind})")
        if len(jax.devices()) < int(cell["chips"]):
            sys.exit(f"{cell['name']} needs {cell['chips']} chips and "
                     f"jax found {len(jax.devices())}")
        common.chip_peaks(dev.device_kind)   # an unknown kind is an error

    t_backend = time.perf_counter()
    runner = importlib.import_module(
        "benchmarks." + RUNNERS[traffic["kind"]])
    reference = common.load_module(
        reference_path, "benchmarks.reference." + config["reference"])
    run = runner.run(cell, config, traffic, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     t_start=T_START, rehearse=args.rehearse,
                     reference=reference,
                     **({"control": True} if args.control else {}))
    run["notes"]["backend_s"] = t_backend - T_START
    run["device"] = common.device_record(run["memory_peak_bytes"])
    run["on_chip"] = dev.platform == "tpu"

    breakdown = None
    if args.trace and run.get("trace_dir") and run["on_chip"]:
        reduced = xplane.reduce(xplane.load(run["trace_dir"]),
                                gap_default=run["gap_default"])
        run["trace"] = reduced
        run["device"]["busy_s"] = reduced["busy_s"]
        run["device"]["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        with open(os.path.join(common.scratch_dir(cell["name"]),
                               "trace_summary.json"), "w") as f:
            json.dump({k: reduced[k] for k in (
                "window_s", "busy_s", "plane", "n_planes", "op_totals",
                "idle_gaps")} | {"modules": sorted(
                    {m[0] for m in reduced["modules"]})}, f, indent=1)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry in metrics_of(manifest, cell["name"], group):
        if entry["source"] == "device_trace" and "trace" not in run:
            continue             # no device trace, no device metric
        value = load_reader(manifest, entry["name"]).read(run)
        if value is not None:
            metrics[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
    log(json.dumps({"checks": run["checks"], "notes": run["notes"],
                    "parity": run.get("parity")}, default=str))
    line = {"correct": bool(run["correct"]),
            "attempted": int(run["attempted"]),
            "failed": int(run["failed"]), "metrics": metrics,
            "device": run["device"]}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {name: {"value": float(value), "limit": float(limit)}
                        for name, (value, limit) in run["compared"].items()}
    for name, pair in line["compared"].items():
        log(f"compared {name}: {pair['value']!r} (limit {pair['limit']!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
