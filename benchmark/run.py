"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Needs a TPU with as many chips as the cell asks
for and exits non-zero, printing no result, without one.  The last line of
standard output is the result object; the numbers `correct` was decided on
go to standard error too, each beside its limit.
"""
from __future__ import annotations

import time

CLOCK0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg):
    print(f"[{time.perf_counter() - CLOCK0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def device_gate(chips, require_tpu=True):
    """The device as JAX reports it; refuses anything but enough TPU chips."""
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu and (dev["platform"] != "tpu" or len(devs) < chips):
        sys.exit(f"benchmark: the cell needs {chips} TPU chip(s); JAX found "
                 f"{dev['count']} device(s) of platform {dev['platform']!r} "
                 f"({dev['kind']}); nothing was run")
    return dev


def compile_cache():
    """JAX's persistent cache at a fixed place inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR says), keeping every program however quickly
    it compiled: a warm run then compiles nothing."""
    import jax

    from paddle_tpu.core.device import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def run_cell(root, workload, seed, seconds, trace, require_tpu=True, clock0=None):
    """Drive one run and return the result object."""
    from benchmark import manifest

    clock0 = CLOCK0 if clock0 is None else clock0
    cell = manifest.load_cell(root, workload)
    chips = cell["cell"]["chips"]
    dev = device_gate(chips, require_tpu)
    peak = manifest.peaks_for(cell["peaks"], dev["kind"])
    log(f"{workload} seed={seed} seconds={seconds} trace={trace} on {dev}; "
        f"compile cache {compile_cache() if require_tpu else 'off'}")
    kind = cell["job"]["kind"]
    if not manifest.NAME_RE.match(kind):
        raise manifest.ManifestError(f"cell kind {kind!r} is not a name")
    # benchmark/<kind>.py, found by name: a new kind of cell is a new file
    runner = importlib.import_module(f"benchmark.{kind}")
    e2e, obs, check = runner.run(cell, seed, seconds, trace, clock0, log)
    obs.update(peak=peak, chips=chips)
    if trace:
        metrics = manifest.read_metrics(cell["per_layer"], obs)
    else:
        metrics = {}
        for entry in cell["end_to_end"]:
            v = e2e.get(entry["name"])
            if v is None:
                raise RuntimeError(f"the run produced no {entry['name']}")
            metrics[entry["name"]] = {"value": float(v), "unit": entry["unit"]}
    device = dict(dev, memory_peak_bytes=int(obs["memory_peak_bytes"]))
    result = {"correct": bool(check["correct"]),
              "attempted": int(check["attempted"]),
              "failed": int(check["failed"]), "metrics": metrics,
              "device": device}
    red = obs.get("trace")
    if trace and red is not None:
        from benchmark import reduce_trace

        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = reduce_trace.breakdown(red)
    result["extra"] = check.get("extra", {})
    result["compared"] = check["numbers"]
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    result = run_cell(ROOT, a.workload, a.seed, a.seconds, bool(a.trace))
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
