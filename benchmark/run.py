"""One run of one benchmark cell, on the chip this process finds.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's data from the seed and warms every program the
cell's traffic runs (`setup_s`); the window then drives that traffic for
`--seconds`. After the window what it produced is checked against the plain
reference. With `--trace 1` the window runs under the
JAX profiler with the program's spans on, and the line carries the cell's
per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and last
`checks`, each number compared beside its limit. Exits 1 without a result
when JAX finds no TPU or fewer chips than the cell asks for.
"""

import argparse
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

import harness  # noqa: E402
import trace_reduce  # noqa: E402

WORKDIR = os.path.join(ROOT, ".bench_work")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _counters() -> dict:
    from hyperspace_tpu.telemetry import metrics

    return dict(metrics.snapshot()["counters"])


def _device(n_chips: int) -> dict:
    import jax

    devs = jax.devices()[:n_chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs), "memory_peak_bytes": int(max(peaks))}


class Readings:
    """What a per-layer metric's reader may read about the traced window."""

    def __init__(self, run, counters, trace, device_kind):
        self.run = run
        self.counters = counters
        self.trace = trace
        self.device_kind = device_kind


def bench_window_start(x):
    return x + 1


def bench_window_end(x):
    return x * 2  # not the start mark's program: XLA would share one executable, and its name


def _marks():
    """The two device programs that mark the traced window's ends
    (trace_reduce.START_MARK, END_MARK), compiled before the window."""
    import jax
    import jax.numpy as jnp

    start, end = jax.jit(bench_window_start), jax.jit(bench_window_end)
    x = jnp.zeros((8,), jnp.float32)
    start(x).block_until_ready()
    end(x).block_until_ready()
    return lambda: start(x).block_until_ready(), lambda: end(x).block_until_ready()


def _profile_options():
    """Device ops and the host's annotations (the program's spans); no
    tracing of every Python call, which would slow the host it measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.raise_error_on_start_failure = True
    return opts


def _read_spans(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _bytes_written() -> dict:
    """This process's writes so far (Linux /proc/self/io): `wchar` as handed to
    write(), `write_bytes` as dirtied for the block layer, and
    `cancelled_write_bytes` of those never sent because the file was
    deleted or truncated first."""
    try:
        with open("/proc/self/io") as f:
            io = dict(line.split(": ") for line in f.read().splitlines())
        return {k: int(io[k]) for k in ("wchar", "write_bytes", "cancelled_write_bytes")}
    except (OSError, KeyError, ValueError):
        return {}


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, workdir: str, log=print) -> dict:
    """One run; returns the result object (the last line's content)."""
    import jax

    t0 = time.monotonic()
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    phases = {}

    def phase(name, fn, *args):
        t = time.monotonic()
        fn(*args)
        phases[name] = time.monotonic() - t

    run = harness.Run(cell, seed, workdir)
    phase("data", run.make_data)
    run.make_session()
    driver = harness.DRIVERS[cell["traffic"]["kind"]](run)
    phase("warmup", driver.warmup, seconds)
    setup_s = time.monotonic() - t0

    spans_path = os.path.join(workdir, "spans.jsonl")
    trace_dir = os.path.join(workdir, "trace")
    if trace:
        mark_start, mark_end = _marks()
    before, written_before = _counters(), _bytes_written()
    if trace:
        os.environ["HYPERSPACE_TRACING"] = "1"
        os.environ["HYPERSPACE_TRACE_FILE"] = spans_path
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    try:
        if trace:
            mark_start()
            t_mark, t_mark_mono = time.time(), time.monotonic()
        win = driver.window(seconds)
    finally:
        if trace:
            mark_end()
            time.sleep(1.0)  # let the device tracer collect the end mark
            jax.profiler.stop_trace()
            os.environ.pop("HYPERSPACE_TRACING", None)
            os.environ.pop("HYPERSPACE_TRACE_FILE", None)
    after, written_after = _counters(), _bytes_written()
    device = _device(cell["chips"])
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in set(after) | set(before)}

    numbers = harness.config_module("reference", cell["config_name"]).check(run)
    limits = harness.config_module("reference", cell["config_name"]).LIMITS
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values()) and len(run.records) > 0

    # A request that fails raises, and the run prints no result.
    result = {"correct": correct, "attempted": len(run.records), "failed": 0}
    if trace:
        spans = _read_spans(spans_path)
        requests = [  # the harness's own request spans, so idle time labels what was in flight
            {"name": f"bench:{r['op']}", "start_s": t_mark + r["t_start"] - t_mark_mono, "duration_s": r["t_done"] - r["t_start"]}
            for r in run.records
        ]
        red = trace_reduce.reduce(trace_reduce.extract(trace_dir), cell["chips"], spans=spans + requests, t_mark=t_mark)
        ctx = Readings(run, delta, red, device["kind"])
        metrics = {}
        for m in cell["per_layer"]:
            value = harness.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        e2e = driver.end_to_end(win)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]} for m in cell["end_to_end"] if m["name"] in e2e}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks

    scan = {k: v for k, v in after.items() if k.startswith("cache.") and (k.endswith(".hits") or k.endswith(".misses"))}
    log(json.dumps({
        "diagnostics": {
            "setup_s": setup_s,
            "setup_phases_s": phases,
            "window_s": win["t_end"] - win["t0"],
            "window_compiles": delta.get("xla.compile_cache.cache_misses", 0),
            "window_cache_hits": delta.get("xla.compile_cache.cache_hits", 0),
            "window_traces": delta.get("xla.compiles.traces", 0),
            "window_bytes_written": {k: written_after[k] - written_before.get(k, 0) for k in written_after},
            "run_bytes_written": written_after,
            "memory_peak_bytes": device["memory_peak_bytes"],
            "scan_cache": scan,
        }
    }, default=str))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: JAX found no TPU (platform {devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"run.py: {len(devices)} chips visible, the cell asks for {cell['chips']}", file=sys.stderr)
        return 1
    from hyperspace_tpu.util.compile_cache import enable_compile_cache

    enable_compile_cache(CACHE_DIR)
    workdir = os.path.join(WORKDIR, args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace), workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
