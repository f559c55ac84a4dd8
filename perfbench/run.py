"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

One process owns the chip: it starts a makisu-tpu ``WorkerServer``,
drives it through ``WorkerClient`` over the Unix socket, and prints, as
the last line of its standard output, one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, traced,
``breakdown``), then ``check``: each count the check compared beside
its limit, also the last lines of the standard error. ``--trace 0``
reports the cell's end-to-end metrics with no poller, sampler or
profiler in the process; ``--trace 1`` reports its per-layer metrics.
It exits non-zero, with no result line, where JAX finds no TPU."""

import time
T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, CHECKOUT)


def say(text: str) -> None:
    print(f"[perfbench] {text}", flush=True)


def main(argv=None, benchmark_path=None, require_tpu=True) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--fault", default="",
                        help="self-check only: break the timed path")
    args = parser.parse_args(argv)

    # The program's own always-on sampler stays off: the traced run
    # brings the benchmark's.
    os.environ["MAKISU_TPU_PROFILE_HZ"] = "0"
    from pbharness import cells, driver, kernels
    cell = cells.Cell(benchmark_path
                      or os.path.join(CHECKOUT, "BENCHMARK.json"),
                      args.workload)
    run = driver.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START)
    parts = run.setup_parts

    from makisu_tpu.ops import backend
    from makisu_tpu.worker import WorkerServer  # noqa: F401 - import cost
    parts["import"] = time.monotonic() - T_START
    t = time.monotonic()
    failure = backend.backend_ready(source="worker")
    if failure:
        say(f"the backend did not come up: {failure}")
        return 3
    import jax
    parts["backend_init"] = time.monotonic() - t
    run.probe = backend.probe_snapshot()
    devices = jax.devices()
    run.device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            say(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                f"found {run.device}")
            return 3
        run.peaks = kernels.peaks_for(devices[0].device_kind)
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _secs, **_kw: run.compile_events.append(time.monotonic())
        if name == "/jax/core/compile/backend_compile_duration" else None)

    run.work_dir = tempfile.mkdtemp(prefix="perfbench-")
    try:
        return _measure(args, cell, run, devices)
    finally:
        shutil.rmtree(run.work_dir, ignore_errors=True)


def _measure(args, cell, run, devices) -> int:
    import numpy as np
    from pbharness import check, driver, faults, sampler, stats, xplane
    parts = run.setup_parts
    if args.fault in faults.BEFORE_WORKER:
        faults.BEFORE_WORKER[args.fault]()
    elif args.fault and args.fault not in faults.AFTER_BUILDS:
        raise SystemExit(f"no fault {args.fault!r}")

    driver.run_cell(run, say)
    say("set-up " + " ".join(f"{k}={v:.2f}s" for k, v in parts.items())
        + f" total={run.setup_s:.2f}s")
    say(f"counted {len(run.counted)} builds in a window of "
        f"{run.window_s:.2f}s ({len(run.builds)} builds in all, "
        f"{sum(b.retries for b in run.counted)} submissions repeated)")

    for kind in ("cold", "rebuild"):
        served = [b.seconds - float(b.terminal.get("queue_wait_seconds", 0))
                  for b in run.builds if b.kind == kind and b.ok]
        if served:
            say(f"{len(served)} {kind} builds in all, seconds outside the "
                f"admission queue: median {stats.percentile(served, 50):.2f}"
                f", 90th percentile {stats.percentile(served, 90):.2f}")
    if len(run.counted) <= 40:
        say("counted builds, seconds each (commit_layer spans): " + "  ".join(
            f"{b.seconds:.2f} ("
            + " ".join(f"{float(d):.2f}" for name, d in b.spans
                       if name == "commit_layer") + ")"
            for b in run.counted))
    if run.samples and len(run.counted) <= 8:
        for b in run.counted:
            say(f"build {b.index} {b.seconds:.2f}s, seconds by innermost "
                "frame: " + "  ".join(
                    f"{label} {seconds:.2f}" for label, seconds in
                    sampler.seconds_by_frame(run.samples, b.t_submit,
                                             b.t_done)))
    if args.fault in faults.AFTER_BUILDS:
        faults.AFTER_BUILDS[args.fault](run)
    t = time.monotonic()
    checker = check.Checker(cell.reference, cell.config["context"])
    picked = check.sample(run, np.random.default_rng([args.seed, 13]),
                          int(cell.traffic.get("check_builds", 2)))
    for build, tree_is_current in picked:
        checker.check_build(build, tree_is_current)
    say(f"check took {time.monotonic() - t:.1f}s")
    failed = sum(1 for b in run.counted if not b.ok)
    correct = bool(picked) and checker.verdict()

    for memory in (d.memory_stats() for d in devices):
        if memory and "peak_bytes_in_use" in memory:
            run.device["memory_peak_bytes"] = max(
                run.device.get("memory_peak_bytes", 0),
                int(memory["peak_bytes_in_use"]))
    wanted = cell.per_layer() if run.trace else cell.end_to_end()
    metrics = {}
    for metric in wanted:
        value = cell.reader(metric["name"])(run)
        if value is not None:
            metrics[metric["name"]] = {"value": value, "unit": metric["unit"]}
    result = {"correct": correct, "attempted": len(run.counted),
              "failed": failed, "metrics": metrics, "device": run.device}
    if run.trace and run.device_trace is not None:
        trace = run.device_trace
        run.device["busy_s"] = trace.busy_s
        run.device["window_s"] = trace.window_s
        spans = sorted((b.t_submit, b.t_done) for b in run.builds)
        by_name: dict = {}
        for text, (_, seconds) in trace.ops.items():
            name = xplane.short_name(text)
            by_name[name] = by_name.get(name, 0.0) + seconds
        result["breakdown"] = {
            "device_ops": [list(kv) for kv in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": xplane.charge_gaps(
                trace, run.samples, run.t_open,
                lambda t: sum(1 for s, e in spans if s <= t <= e))}
    # Each number compared, beside its limit: the last key of the
    # result and the last lines of the standard error.
    result["check"] = checker.numbers()
    print("\n".join(f"[perfbench] {line}" for line in checker.lines()),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # The worker's daemon threads (backend probe, resource sampler) can
    # be inside native code when the interpreter tears down, and abort
    # the process after the result is out; every thread and file of
    # this run is already closed, so leave without the teardown.
    os._exit(code)
