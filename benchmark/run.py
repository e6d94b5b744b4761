#!/usr/bin/env python3
"""One process, one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

looks the cell up in ``BENCHMARK.json``, loads its configuration
(``benchmark/configs/<config>.json``) and traffic
(``benchmark/traffic/<traffic>.json``), hands both to the driver the
traffic file names (``benchmark/drivers/<driver>.py``), and prints as
the LAST line of its standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end
metrics (readers in ``benchmark/e2e_metrics/``), with ``--trace 1`` its
per-layer metrics (``benchmark/layer_metrics/``). A cell, a
configuration, a traffic mix or a metric is added as new files and new
entries of ``BENCHMARK.json``; nothing here names one.

It needs a TPU with at least the cell's ``chips`` and fails without.
``--rehearse`` (CPU only, explicit) runs the cell's ``tiny`` sizes for
two seconds to prove control flow, and prints every device metric as
null: it is never a number.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL_SECONDS = 2


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true",
                    help="put the lower-precision control in the program's "
                         "place where `correct` is decided: the run has to "
                         "come out not correct (never in a benchmark run)")
    return ap.parse_args(argv)


def readers(package, names):
    """The metric readers of one directory, by the metric's name."""
    found = {}
    folder = os.path.join(ROOT, "benchmark", package)
    for entry in sorted(os.listdir(folder)):
        if entry.endswith(".py") and not entry.startswith("_"):
            mod = importlib.import_module(
                "benchmark.%s.%s" % (package, entry[:-3]))
            if mod.NAME in names:
                found[mod.NAME] = mod
    return found


def measure(ctx, entries, package, null):
    """``{name: {value, unit}}`` for the metrics of ``entries`` that this
    cell reports. A reader that finds nothing returns None and its
    metric is left out."""
    cell, reported = ctx.cell["name"], set(ctx.end_to_end())
    wanted = {m["name"]: m for m in entries
              if cell in m.get("workloads", [cell])
              and m.get("moves", m["name"]) in reported}
    out = {}
    for name, mod in readers(package, wanted).items():
        value = mod.compute(ctx)
        if value is not None:
            out[name] = {"value": None if null else float(value),
                         "unit": wanted[name]["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    sys.path.insert(0, ROOT)
    spec_path = os.path.join(ROOT, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    if args.rehearse:
        args.seconds = REHEARSAL_SECONDS
        os.environ["JAX_PLATFORMS"] = "cpu"
    elif args.seconds is None:
        args.seconds = spec["run_seconds"]
    from benchmark import harness, launch_join, peaks
    cell = harness.by_name(spec["workloads"], args.workload, "workload")
    config_entry = harness.by_name(spec["configs"], cell["config"],
                                   "configuration")
    config = harness.load_json(ROOT, config_entry["file"])
    traffic = harness.load_json(ROOT, "benchmark", "traffic",
                                cell["traffic"] + ".json")
    if args.rehearse:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % cell["chips"])

    from mxnet_tpu import runtime
    cache_dir = runtime.enable_compile_cache()
    import jax
    # where set-up's seconds go: seconds since the process started,
    # taken when the named part ended; a driver adds its own
    stamps = {"imported": time.perf_counter() - T_START}
    devices = jax.devices()
    stamps["devices"] = time.perf_counter() - T_START
    if not args.rehearse:
        if devices[0].platform != "tpu":
            sys.exit("benchmark: needs a TPU, JAX found %s (%s); "
                     "--rehearse is the only CPU mode"
                     % (devices[0].platform, devices[0].device_kind))
    if len(devices) < cell["chips"]:
        sys.exit("benchmark: cell %s needs %d chips, JAX found %d"
                 % (cell["name"], cell["chips"], len(devices)))

    ctx = harness.Context(spec, cell, config, traffic, args, T_START)
    ctx.raw["setup_stamps"] = stamps
    if not args.rehearse:
        ctx.peak = peaks.peak(devices[0].device_kind)
    driver = importlib.import_module(
        "benchmark.drivers." + traffic["driver"])
    verdict = driver.run(ctx)

    if args.trace:
        # read back once the window has closed and the driver is done
        harness.reduce_trace(ctx)
        metrics = measure(ctx, spec["per_layer"], "layer_metrics",
                          args.rehearse)
    else:
        metrics = measure(ctx, spec["end_to_end"], "e2e_metrics",
                          args.rehearse)
    device = harness.device_record(ctx)
    result = {"correct": bool(verdict["correct"]),
              "attempted": int(verdict["attempted"]),
              "failed": int(verdict["failed"]),
              "metrics": metrics, "device": device}
    if ctx.trace is not None and ctx.trace.devices:
        # idle time under the program's own spans, each program joined
        # to its launch and moved by its lead, where the program numbers
        # its launches; the benchmark's own spans where it does not
        result["breakdown"] = {
            "device_ops": ctx.trace.top_ops(10),
            "idle_gaps": launch_join.idle_gaps(ctx, 10)
            or ctx.trace.idle_gaps(
                10, unnamed=ctx.raw.get("unnamed_gap", "unattributed"))}
    if args.rehearse:
        result["rehearsal"] = True
    # every number that decided ``correct`` beside its limit: the last
    # key of the line and the last lines of standard error
    result["compared"] = verdict.get("compared", {})
    # what a person debugging wants, on an earlier line and in out/
    detail = {"cell": cell["name"], "seed": args.seed,
              "seconds": args.seconds, "compile_cache": cache_dir,
              "problems": verdict["problems"],
              "compiles": ctx.compiles.count,
              "compile_s": ctx.compiles.seconds,
              "raw": {k: v for k, v in ctx.raw.items()
                      if k not in ("streams", "stats0", "stats1")}}
    print(json.dumps(detail, default=str), flush=True)
    os.makedirs(harness.OUT, exist_ok=True)
    with open(os.path.join(harness.OUT, "%s.seed%d.trace%d.json" % (
            cell["name"], args.seed, args.trace)), "w") as f:
        json.dump(dict(detail, raw=ctx.raw, result=result), f, default=str)
    sys.stdout.flush()
    for name, pair in result["compared"].items():
        print("compared %s %s limit %s" % (name, pair["value"],
                                           pair["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
