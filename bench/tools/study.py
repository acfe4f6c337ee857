"""Readings that set a cell's limit of ``correct``, in one process on the
chip.

    python3 bench/tools/study.py --config C --traffic T \\
        --seeds 1,2,3 --seconds S [--control]

It runs the cell's driver once per seed, as ``bench/run.py`` would, and
prints each run's compared numbers; with ``--control`` also the
control's (the reference in a lower precision in the program's place, on
the same inputs). It is not part of a benchmark run.
"""
import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from benchlib.common import (SRC, Cell, device_info,  # noqa: E402
                             enable_compile_cache, load_json, log, peaks_for,
                             tpu_devices)


def _cell(config, traffic):
    conf = load_json(BENCH / "configs" / f"{config}.json")
    chips = conf.get("ranks", 1)
    bench = {"configs": [{"name": config,
                          "file": f"bench/configs/{config}.json"}],
             "workloads": [{"name": f"{config}.{traffic}", "config": config,
                            "traffic": traffic, "chips": chips}],
             "end_to_end": [], "per_layer": []}
    return Cell(f"{config}.{traffic}", bench=bench)


def _ctx(cell, seed, seconds, devices, control=False):
    import types
    return types.SimpleNamespace(
        cell=cell, seed=seed, seconds=seconds, trace=False, devices=devices,
        t_start=time.perf_counter(), peaks=peaks_for(devices[0].device_kind),
        trace_dir=None, options=None, control=control,
        device_info=lambda: device_info(devices))


def readings(args, devices):
    cell = _cell(args.config, args.traffic)
    driver = cell.driver()
    for seed in [int(s) for s in args.seeds.split(",")]:
        res = driver.run(_ctx(cell, seed, args.seconds, devices,
                              args.control))
        print(json.dumps({"seed": seed, "correct": res.correct,
                          "readings": res.readings,
                          "metrics": res.metrics,
                          "memory_peak_bytes":
                              res.device["memory_peak_bytes"]}), flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(SRC))
    enable_compile_cache()
    cell = _cell(args.config, args.traffic)
    devices = tpu_devices(cell.chips)
    log(f"[study] readings {args.config}/{args.traffic} on "
        f"{devices[0].device_kind} x{len(devices)}")
    readings(args, devices)


if __name__ == "__main__":
    main()
