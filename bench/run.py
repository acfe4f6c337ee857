"""One run of one benchmark cell, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix, driver, reference and per-layer metric readers
are files found by the names there. With ``--trace 0`` the result line
holds the cell's end-to-end metrics; with ``--trace 1`` a profiled run's
per-layer metrics, device busy time and breakdown. The last line of
standard output is the result, one JSON object; the comparisons that
decided ``correct`` are the last lines of standard error. A machine with
no TPU, or fewer chips than the cell asks for, gets an error and no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from benchlib.common import (SRC, BenchError, Cell, device_info,  # noqa: E402
                             emit, enable_compile_cache, load_module, log,
                             peaks_for, tpu_devices)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer(cell, records):
    """Each per-layer metric of the cell, from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(records)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, trace_dir, cell=None, devices=None, peaks=None):
    """One run; returns the result line's object and the comparisons.
    ``cell``, ``devices`` and ``peaks`` stand in for the cell of
    ``BENCHMARK.json``, the look for a chip and the peak table only in the
    benchmark's own tests."""
    cell = cell or Cell(args.workload)
    if not SRC.is_dir():
        raise BenchError(f"no program next to the benchmark ({SRC})")
    sys.path.insert(0, str(SRC))
    import jax
    cache = enable_compile_cache()
    devices = devices or tpu_devices(cell.chips)
    log(f"[bench] {cell.name}: {devices[0].device_kind} x{len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache}")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    ctx = SimpleNamespace(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, t_start=T_START,
        peaks=peaks or peaks_for(devices[0].device_kind),
        trace_dir=trace_dir, options=options,
        device_info=lambda: device_info(devices))
    res = cell.driver().run(ctx)
    result = {"correct": res.correct, "attempted": res.attempted,
              "failed": res.failed}
    if args.trace:
        red = res.records.trace
        result["metrics"] = per_layer(cell, res.records)
        res.device["busy_s"] = sum(red.busy_s(d) for d in red.devices) \
            / len(red.devices)
        res.device["window_s"] = red.window_s
        result["device"] = res.device
        result["breakdown"] = {"device_ops": red.top_ops(),
                               "idle_gaps": red.idle_gaps()}
    else:
        result["metrics"] = {m["name"]: {"value": res.metrics[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = res.device
    return result, res.checks


def main(argv=None):
    args = parse(argv)
    try:
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as d:
            result, checks = run(args, d)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
