"""Paths, cell lookup, seeds, the device check and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SRC = ROOT / "src"
# JAX's persistent compilation cache: one fixed path inside the checkout
# (the path is part of an entry's key, so a directory that moved would
# never hit), unless JAX_COMPILATION_CACHE_DIR names another.
CACHE_DIR = ROOT / ".bench_cache" / "jax"


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown cell, ...)."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    """Import a harness file by path (metric readers, references and
    drivers are found by name, not imported as a package)."""
    path = pathlib.Path(path)
    name = name or "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with everything the
    harness finds by its names: the configuration file, its reference,
    the traffic mix and the per-layer metrics the cell reports."""

    def __init__(self, name, bench=None, root=ROOT,
                 traffic_dir=BENCH / "traffic"):
        bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"unknown workload {name!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.name = name
        self.root = root
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        cfg_entry = configs[self.entry["config"]]
        self.config_path = root / cfg_entry["file"]
        self.config = load_json(self.config_path)
        self.traffic_path = traffic_dir / f"{self.entry['traffic']}.json"
        self.traffic = load_json(self.traffic_path)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def reference_path(self):
        """``<config>.ref.py`` beside the configuration file, unless the
        file names another (a test-size copy of a configuration)."""
        if "reference" in self.config:
            return self.root / self.config["reference"]
        return self.config_path.with_name(
            self.config_path.name[:-len(".json")] + ".ref.py")

    def reference(self):
        return load_module(self.reference_path)

    def driver(self):
        return load_module(BENCH / "benchlib" / f"{self.config['driver']}.py",
                           "bench_driver_" + self.config["driver"])


def seed_key(seed):
    """A JAX PRNG key from a seed of any size (seeds may exceed
    32 bits): both 32-bit halves go into the key."""
    import jax
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def enable_compile_cache():
    """Turn JAX's persistent compilation cache on for every program, however
    small or quick to compile, so that a cell's second run compiles
    nothing. Returns the directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(CACHE_DIR)
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips):
    """The first ``chips`` TPU devices; raises where JAX finds no TPU or
    fewer chips than the cell asks for. Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def device_info(devices):
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def peaks_for(kind):
    """Published peaks of one chip of ``device_kind`` ``kind``. A kind that
    is not in ``peaks.json`` is an error, never a default."""
    table = load_json(BENCH / "peaks.json")["chips"]
    if kind not in table:
        raise BenchError(f"no published peaks for device kind {kind!r}; "
                         f"bench/peaks.json has {sorted(table)}")
    return table[kind]


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def emit(result, checks):
    """Print the comparisons that decided ``correct`` as the last lines of
    standard error, and the result as the last line of standard output,
    with the comparisons under ``checks``, its last key."""
    for name, c in checks.items():
        log(f"[check] {name} = {c['value']!r} (limit {c['limit']!r}, "
            f"{c['rule']})")
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
