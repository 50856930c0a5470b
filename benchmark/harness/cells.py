"""Find a cell, its configuration, its traffic mix and its per-layer
metrics by the names in BENCHMARK.json.

Everything that belongs to one configuration, one mix or one metric is a
file of its own, found by name:

    configs/<config>.json        the deployment: preset sizes, guarantees,
                                 assumed sizes, the program environment
    traffic/<traffic>.json       the mix: loop, request unit, invalid
                                 share, warm-up
    traffic/units/<unit>.py      the mix's request unit (traffic.py)
    traffic/loops/<kind>.py      the mix's loop (loops.py)
    metrics/<metric>.py          a reader: read(window) -> number or None

so a later change adds a cell, a mix, a unit, a loop or a metric with
new files and new BENCHMARK.json entries only.  A mix with a key that
neither the harness nor its unit or loop reads is refused, so that no
parameter is silently ignored.
"""

import importlib.util
import json
import os

# keys of a mix the harness itself reads; "request" and "loop" also
# take the keys their unit's and loop's modules declare in KEYS
MIX_KEYS = {"why", "loop", "request", "priority", "want_per_set",
            "programs", "warmup", "invalid", "trace_seconds"}
WARMUP_KEYS = {"requests"}
INVALID_KEYS = {"every", "chunks"}
PROGRAMS = {"batched", "per_set"}


class CellError(Exception):
    pass


def module(bench_dir, where, name):
    """The module <bench_dir>/<where>/<name>.py."""
    path = os.path.join(bench_dir, where, name + ".py")
    if not os.path.exists(path):
        raise CellError(f"no {where}/{name}.py in {bench_dir}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + (where + "_" + name).replace("/", "_").replace(".", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of BENCHMARK.json's `workloads`, resolved."""

    def __init__(self, name, chips, config, traffic, end_to_end, per_layer,
                 bench_dir):
        self.name = name
        self.chips = chips
        self.config = config
        self.traffic = traffic
        self.end_to_end = end_to_end      # [metric entry] this cell reports
        self.per_layer = per_layer        # [metric entry] this cell reports
        self.bench_dir = bench_dir
        self.unit = module(bench_dir, "traffic/units",
                           traffic["request"]["unit"])
        self.loop = module(bench_dir, "traffic/loops",
                           traffic["loop"]["kind"])
        check_mix(traffic, self.unit, self.loop)

    def reader(self, metric_name):
        """The `read(window)` function of metrics/<metric_name>.py."""
        return module(self.bench_dir, "metrics", metric_name).read


def _unknown(what, got, allowed):
    extra = sorted(set(got) - set(allowed))
    if extra:
        raise CellError(f"{what}: unknown keys {extra}")


def check_mix(mix, unit, loop):
    """Refuse a mix with a key nothing reads or a value out of range."""
    _unknown("mix", mix, MIX_KEYS)
    _unknown("mix request", mix["request"], {"unit"} | unit.KEYS)
    _unknown("mix loop", mix["loop"], {"kind"} | loop.KEYS)
    _unknown("mix warmup", mix["warmup"], WARMUP_KEYS)
    _unknown("mix programs", mix["programs"], PROGRAMS)
    inv = mix.get("invalid")
    if inv is not None:
        _unknown("mix invalid", inv, INVALID_KEYS)
        bad = [c for c in inv["chunks"] if c not in ("first", "last")]
        if bad or not inv["chunks"] or int(inv["every"]) < 1:
            raise CellError(f"mix invalid: {inv} (chunks are 'first' or "
                            f"'last', every at least 1)")


def _load_json(path, what):
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise CellError(f"{what}: {e}") from e


def _reports(metric, cell_name):
    wl = metric.get("workloads")
    return wl is None or cell_name in wl


def load(bench_dir, name, benchmark_json=None):
    """Resolve cell `name` from BENCHMARK.json (by default the one at
    the root of the checkout, beside the benchmark's directory)."""
    if benchmark_json is None:
        benchmark_json = os.path.join(os.path.dirname(bench_dir),
                                      "BENCHMARK.json")
    bench = _load_json(benchmark_json, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise CellError(f"no workload named {name!r} in BENCHMARK.json")
    config = _load_json(
        os.path.join(bench_dir, "configs", entry["config"] + ".json"),
        f"config {entry['config']}")
    traffic = _load_json(
        os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"),
        f"traffic {entry['traffic']}")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, int(entry["chips"]), config, traffic, e2e, per_layer,
                bench_dir)
