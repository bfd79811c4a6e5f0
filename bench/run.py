"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json``: the cell's
configuration (``bench/configs/<config>.json`` and the weights, reference
and work counts beside it in ``<config>.py``), its traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the driver in
``bench/lib/``), the cell's own numbers (``bench/cells/<cell>.json``: its
rate and the limits of its checks) and each per-layer metric's reader
(``bench/metrics/<metric>.py``).  A new cell, mix or metric is new files.

The run refuses anything but a TPU with the chips the cell asks for.  It
makes the weights from ``--seed`` on the device, warms up every shape its
traffic uses (counted as ``setup_s``), measures for ``--seconds``, checks
what the window served against the plain reference, and prints one JSON
line last: with ``--trace 0`` the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window and the benchmark's spans.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_key(seed: int):
    """A JAX key from any whole `seed`: the low 32 bits, then the rest
    and the sign folded in."""
    import jax
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    rest = abs(seed) >> 32
    while rest:
        key = jax.random.fold_in(key, rest & 0x7FFFFFFF)
        rest >>= 31
    return jax.random.fold_in(key, 1) if seed < 0 else key


class Cell:
    """What one run knows: the cell's files, its arguments, the chip."""

    def __init__(self, bench: dict, name: str, seed: int, seconds: float,
                 trace: bool) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.bench, self.name = bench, name
        self.workload = cells[name]
        conf = {c["name"]: c for c in bench["configs"]}[
            self.workload["config"]]
        self.config = load_json(ROOT, conf["file"])
        self.config_module = load_module(
            os.path.join(ROOT, os.path.splitext(conf["file"])[0] + ".py"),
            "bench_config")
        self.mix = load_json(BENCH, "traffic",
                             self.workload["traffic"] + ".json")
        self.cell = load_json(BENCH, "cells", name + ".json")
        self.vocab = self.config["vocab_size"]
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devs = None
        self.peaks = None
        self.meter = None
        self.setup_s = None
        self.trace_data = None

    def start(self) -> None:
        from lib.device import CompileMeter, peaks, require_tpu
        self.devs = require_tpu(self.workload["chips"])
        self.peaks = peaks(self.devs[0].device_kind)
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        # every program, however quick to compile, goes to the cache: each
        # run after a cell's first then finds all of its set-up there
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        self.meter = CompileMeter()

    def arch(self):
        """The program's own config of the same name."""
        from repro.configs import get_config
        return get_config(self.config["program_arch"])

    @functools.cached_property
    def _param_fn(self):
        import jax
        mod, c = self.config_module, self.config
        return jax.jit(lambda key: mod.make_params(c, key))

    def make_params(self):
        """The weights of this seed, made on the device in one call."""
        import jax
        return jax.block_until_ready(self._param_fn(seed_key(self.seed)))

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - T_START
        return self.setup_s

    def device_record(self) -> dict:
        from lib.device import device_record
        return device_record(self.devs)

    @contextlib.contextmanager
    def tracing(self):
        """With ``--trace 1``, a profiler trace of the block, reduced."""
        if not self.trace:
            yield
            return
        import jax
        from lib import trace as tr
        d = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1            # the benchmark's annotations
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                yield
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            t1 = time.perf_counter()
            try:
                self.trace_data = tr.load(d)
            finally:
                shutil.rmtree(d, ignore_errors=True)
            print(f"trace: written in {t1 - t0:.1f} s, read in "
                  f"{time.perf_counter() - t1:.1f} s", file=sys.stderr)


def metric_applies(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """A metric with a ``workloads`` key is the listed cells'; an end-to-end
    metric without one is every cell's, a per-layer one every cell's that
    reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def disk_written() -> int | None:
    """Bytes this process has had written to storage so far (Linux's
    ``write_bytes``), or None where the kernel does not say."""
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def per_layer(cell: Cell, result: dict, e2e_names: set[str]) -> dict:
    obs = dict(result["obs"], trace=cell.trace_data, config=cell.config,
               config_module=cell.config_module, peaks=cell.peaks)
    out = {}
    for m in cell.bench["per_layer"]:
        if not metric_applies(m, cell.name, e2e_names):
            continue
        reader = load_module(os.path.join(BENCH, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(obs)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = Cell(bench, args.workload, args.seed, args.seconds,
                bool(args.trace))
    from lib.device import NoChipError
    try:
        cell.start()
    except NoChipError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    driver = importlib.import_module("lib." + cell.mix["kind"])
    result = driver.run(cell)

    e2e_names = {m["name"] for m in bench["end_to_end"]
                 if metric_applies(m, cell.name, set())}
    if args.trace:
        metrics = per_layer(cell, result, e2e_names)
        from lib import trace as tr
        td = cell.trace_data
        counts: dict[str, int] = {}
        for evs in td.modules.values():
            for name, _, _ in evs:
                counts[name] = counts.get(name, 0) + 1
        print(f"trace: planes {sorted(td.modules)}, {len(td.host)} host "
              f"spans, modules {sorted(counts.items(), key=lambda kv: -kv[1])[:8]}",
              file=sys.stderr)
        result["device"].update(busy_s=tr.busy_s(td), window_s=tr.window_s(td))
        breakdown = {"device_ops": tr.top_ops(td), "idle_gaps": tr.idle_gaps(td)}
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {n: {"value": result["e2e"][n], "unit": units[n]}
                   for n in sorted(e2e_names) if n in result["e2e"]}
        missing = e2e_names - set(metrics)
        if missing:
            print(f"bench: no reading of {sorted(missing)}", file=sys.stderr)
            result["correct"] = False
        breakdown = None
    print(f"disk: {disk_written()} bytes written by this run",
          file=sys.stderr)
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']} (limit {chk['limit']})",
              file=sys.stderr)
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": result["device"]}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = result["checks"]
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
