"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json`` at the root
of the checkout: the cell's configuration (``bench/configs/<config>.json``),
its traffic mix (``bench/traffic/<traffic>.json``, which names its load
loop in ``bench/loops/``), its limits (``bench/limits/<cell>.json``) and, with
``--trace 1``, one reader per per-layer metric (``bench/metrics/<name>.py``).

``--trace 0`` reports the cell's end-to-end metrics, taken with the profiler
off.  ``--trace 1`` profiles a window of ``trace_seconds`` (from the traffic
file) with no program trace active, then makes one more request under an
enabled ``repro.obs.RunTrace`` for the program's counters and spans, and
reports the per-layer metrics.  Either way the run then checks what its
window produced against the plain references in ``bench/ref``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared beside its limit).
Without a TPU, or with fewer chips than the cell needs, it prints no such
line and exits 1.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's entry, configuration, traffic, limits and metric lists."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = next(w for w in spec["workloads"] if w["name"] == name)
    centry = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def read(*parts):
        with open(os.path.join(root, *parts)) as f:
            return json.load(f)

    def mine(m):
        return name in m.get("workloads", [name])

    return {"cell": cell,
            "config": read(centry["file"]),
            "traffic": read("bench", "traffic", cell["traffic"] + ".json"),
            "limits": read("bench", "limits", name + ".json"),
            "end_to_end": [m for m in spec["end_to_end"] if mine(m)],
            "per_layer": [m for m in spec["per_layer"] if mine(m)]}


def _import(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def peaks(kind: str, root: str = ROOT) -> dict:
    """Published per-chip peaks of ``kind`` (``bench/peaks.json``); a
    device the table does not know is an error, not a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def enable_cache(root: str) -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when
    set, else the fixed ``<checkout>/.jax_cache``; every program is kept."""
    import jax

    path = os.environ.get(CACHE_ENV) or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def run_cell(c: dict, seed: int, seconds: float, trace: bool, devices,
             engine=None, t_start: float = T_START, counter=None) -> dict:
    """Set up, run the window, read the metrics, check.  Returns the result
    object; the caller prints it."""
    import jax

    from bench import check, trace_reduce
    from bench.compiles import CompileCounter

    counter = counter or CompileCounter()
    cfg, traffic = c["config"], c["traffic"]
    mod = importlib.import_module("bench.loops." + traffic["loop"])
    drv = mod.Loop(cfg, traffic, seed, devices, engine)
    drv.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s={setup_s!r}")

    ctx, breakdown, dev_extra = {}, None, {}
    if not trace:
        counter.arm()
        win = drv.window(seconds)
        log(f"window_compiles={counter.disarm()} "
            f"(compile_s={counter.compile_s!r})")
    else:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no per-call Python events
            jax.profiler.start_trace(logdir, profiler_options=opts)
            counter.arm()
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                win = drv.window(min(seconds, traffic["trace_seconds"]))
            ctx["window_compiles"] = counter.disarm()
            jax.profiler.stop_trace()
            red = trace_reduce.reduce(trace_reduce.load(
                trace_reduce.find_xplane(logdir)))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        ctx["trace"] = red
        for plane, pct in sorted(red["idle_pct_per_device"].items()):
            log(f"device_idle_pct[{plane}]={pct!r}")
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        dev_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        ctx.update(drv.traced_request())
    for k, v in win["notes"].items():
        log(f"{k}={v!r}")
    peak = peak_bytes(devices)

    if trace:
        metrics = {}
        for m in c["per_layer"]:
            reader = _import(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"])
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        known = dict(win["e2e"], setup_s=setup_s, peak_hbm_gb=peak / 1e9)
        metrics = {m["name"]: {"value": float(known[m["name"]]),
                               "unit": m["unit"]}
                   for m in c["end_to_end"]}

    numbers = drv.check()
    correct, checks = check.judge(numbers, c["limits"])
    for name, nv in checks.items():
        log(f"check {name}={nv['value']!r} limit={nv['limit']!r}")
    d0 = devices[0]
    out = {"correct": bool(correct), "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics,
           "device": {"platform": d0.platform, "kind": d0.device_kind,
                      "count": c["cell"]["chips"],
                      "memory_peak_bytes": peak, **dev_extra}}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        c = load_cell(args.workload)
    except (OSError, StopIteration, KeyError, ValueError) as e:
        log(f"cannot load cell {args.workload!r}: {type(e).__name__}: {e}")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro  # noqa: F401 - the system under test
    except ImportError as e:
        log(f"the program is not in this checkout: {e}")
        return 1
    import jax

    devices = jax.devices()
    chips = c["cell"]["chips"]
    if devices[0].platform != "tpu":
        log(f"needs a TPU, JAX found {devices[0].platform!r}")
        return 1
    if len(devices) < chips:
        log(f"cell {args.workload} needs {chips} chips, found {len(devices)}")
        return 1
    try:
        peak = peaks(devices[0].device_kind)
    except KeyError as e:
        log(str(e))
        return 1
    log(f"device {devices[0].device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, compile cache {enable_cache(ROOT)}, peaks "
        f"{peak}")
    out = run_cell(c, args.seed, args.seconds, bool(args.trace),
                   devices[:chips])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
