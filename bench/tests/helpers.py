"""Test-size cells and a CPU stand-in for the chip's trace."""
import types

from benchlib.common import BENCH, Cell, load_json, load_module
from benchlib.trace import Event

DATA = BENCH / "tests" / "data"
PEAKS = load_json(BENCH / "peaks.json")["chips"]["TPU v5 lite"]

KERNEL_E2E = ["kernel_step_us", "setup_s"]
KERNEL_LAYER = ["moe_dispatch_roofline", "mfu.kernel", "idle_share.kernel"]


def cell(config, traffic, e2e, layer, chips=4):
    """A cell of a test-size configuration and mix, as BENCHMARK.json would
    describe it."""
    metric = lambda n: {"name": n, "unit": "x"}
    bench = {"configs": [{"name": "tiny", "file": f"bench/tests/data/{config}.json"}],
             "workloads": [{"name": "tiny.cell", "config": "tiny",
                            "traffic": traffic, "chips": chips}],
             "end_to_end": [metric(n) for n in e2e],
             "per_layer": [metric(n) for n in layer]}
    return Cell("tiny.cell", bench=bench, traffic_dir=DATA / "traffic")


def run(c, seed, seconds, trace, devices):
    """A whole run of ``bench/run.py`` but for the look for a chip."""
    import tempfile
    mod = load_module(BENCH / "run.py", "bench_run")
    args = types.SimpleNamespace(workload=c.name, seed=seed, seconds=seconds,
                                 trace=trace)
    with tempfile.TemporaryDirectory() as d:
        return mod.run(args, d, cell=c, devices=devices, peaks=PEAKS)


def cpu_events(logdir):
    """The CPU backend's trace in the chip's form: XLA's op events (those
    that carry an ``hlo_op``) become the ops of one device."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if any(k == "hlo_op" for k, _ in e.stats):
                    out.append(Event("/device:TPU:0", "XLA Ops", e.name,
                                     float(e.start_ns), float(e.duration_ns)))
                elif e.name.startswith("bench."):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out
