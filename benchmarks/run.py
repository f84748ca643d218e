"""One cell of the benchmark, once.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. It sets no platform and fails where the backend is not a
TPU, holds fewer chips than the cell asks for, or is of a kind that
`benchmarks/peaks.json` does not list. It builds the cell from `--seed`
(inputs, weights, arrival schedule), warms up the cell's own shapes,
measures for `--seconds`, checks what the window produced outside the
window, and prints as its last line one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, traced, `breakdown`.
With `--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics; every number compared is beside its
limit on an earlier line, under the result line's last key `checks`
(`name: [value, limit]`) and on the last lines of standard error; every
timing's sample count is on an earlier line.
`--control <name>` (the builder's, never the driver's) runs the cell
with the lower-precision overrides its configuration file lists, to
show that `correct` then comes out false.
"""

import time

T0 = time.perf_counter()  # set-up counts from here: imports included

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os.path as osp  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

from benchmarks import harness  # noqa: E402


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, control: dict | None, device: dict,
             t0: float) -> dict:
    """Builds, warms up, measures and verifies one cell on the devices
    jax has, and returns the result line's object. `device` is the
    `device` block so far (platform, kind, count)."""
    compiles = harness.CompileCounter()
    driver = harness.load_driver(cell["mix"]["driver"])
    ctx = driver.build(cell, seed, seconds=seconds, control=control,
                       trace=trace)
    try:
        driver.warm_up(ctx)
        setup_s = time.perf_counter() - t0
        tracer = None
        if trace:
            tracer = harness.Tracer(
                cell["name"], cell["chips"], tuple(driver.HOST_SPANS),
                driver.UNATTRIBUTED, harness.metric_scopes())
        compiles.armed = True
        window = driver.measure(ctx, seconds, tracer)
        compiles.armed = False
        # the program's peak, read before the reference touches the chip
        device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
            cell["chips"]))
        t_verify = time.perf_counter()
        checks = driver.verify(ctx, window)
        verify_s = time.perf_counter() - t_verify
    finally:
        driver.close(ctx)
    checks.append(harness.check(
        "compilations_in_window", compiles.count, 0, "=="))
    for c in checks:
        harness.say(**c)
    harness.say(samples=window["samples"], setup_s=setup_s, verify_s=verify_s,
                compile_seconds_in_process=compiles.seconds_total)

    window["memory_peak_bytes"] = device["memory_peak_bytes"]
    metrics: dict = {}
    breakdown = None
    if trace:
        reduced = window["trace"]
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["top_ops"][:10],
                     "idle_gaps": reduced["gaps"][:10]}
        harness.say(trace={k: v for k, v in reduced.items()
                           if k not in ("top_ops", "gaps")})
        for m in harness.metrics_of_cell(bench, cell["name"], "per_layer"):
            value = harness.read_layer_metric(m["name"], window)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(window["end_to_end"], setup_s=setup_s)
        for m in harness.metrics_of_cell(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": all(c["ok"] for c in checks),
            "attempted": int(window["attempted"]),
            "failed": int(window["failed"]),
            "metrics": metrics, "device": device,
            "checks_failed": [c["check"] for c in checks if not c["ok"]]}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # every number compared beside its limit: the line's last key
    line["checks"] = {
        c["check"]: [_shown(c["value"]), c["limit"]] for c in checks}
    return line


def _shown(value):
    """A compared value as the result line shows it: a float that is no
    number goes as text (`NaN` is not JSON)."""
    if isinstance(value, float) and not math.isfinite(value):
        return str(value)
    return value


def run(argv: list[str] | None = None, *, t0: float = T0) -> dict:
    """Parses the command line, looks for the chips and runs the cell."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed is a non-negative whole number")

    bench = harness.load_benchmark()
    cell = harness.load_cell(args.workload, bench)
    control = None
    if args.control is not None:
        control = cell["config_data"]["lower_precision"][args.control]
    device, peaks = harness.device_info(cell["chips"])
    cache_dir = harness.enable_compile_cache()
    harness.say(start=args.workload, seed=args.seed, seconds=args.seconds,
                trace=args.trace, control=control, device=device,
                peaks=peaks, compile_cache=cache_dir)
    return run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), control=control, device=device,
                    t0=t0)


if __name__ == "__main__":
    result = run()
    print(json.dumps(result, default=harness.plain), flush=True)
    for name, (value, limit) in result["checks"].items():
        print(f"{name} {value} limit {limit}", file=sys.stderr, flush=True)
