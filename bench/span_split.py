"""Where a chat cell's host time goes, by the program's own spans, in one
process on the chip.

    python bench/span_split.py traced --workload <cell> --seeds 1,2 --seconds 51
    python bench/span_split.py cost   --workload <cell> --seeds 1 --seconds 51

``traced`` runs the cell as ``run.py --trace 1`` does, with the program's
spans (`repro.core.spans`) on inside the profiler trace and kept beside
the benchmark's annotations.  Per seed it prints the cell's per-layer
metrics as ``run.py`` reads them, the split of `kv_host_ms.chat` by span
(`lib/program_spans.py`), the device's idle gaps between decode steps
against the median gap between tokens, each span's seconds, and the
idle-gap breakdown, where the program's spans now take the stretches
they cover.  ``cost`` runs the cell with the profiler off, spans off, on,
on and off in turn on each seed, and prints each run's end-to-end
metrics: what spans cost when they are on.  The benchmark's own runs read
no program span.  Each prints one JSON line per run, and writes them to
``chiprun_out/`` as well.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from run import Cell, ROOT, load_json, metric_applies, per_layer


def _out(kind: str, workload: str, line: dict) -> None:
    print(json.dumps(line), flush=True)
    d = os.path.join(ROOT, "chiprun_out")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"span_split.{kind}.{workload}.jsonl"),
              "a") as f:
        f.write(json.dumps(line) + "\n")


class SpanCell(Cell):
    """A cell whose window runs with the program's spans on (or off, with
    `program_spans` false); traced, the trace keeps them."""

    program_spans = True

    @contextlib.contextmanager
    def tracing(self):
        import jax
        from repro.core import spans
        from lib import program_spans, trace as tr
        if self.program_spans:
            spans.enable()
        try:
            if not self.trace:
                yield
                return
            d = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.host_tracer_level = 1
            opts.python_tracer_level = 0
            jax.profiler.start_trace(d, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
                    yield
            finally:
                t0 = time.perf_counter()
                jax.profiler.stop_trace()
                try:
                    self.trace_data = tr.load(d)
                    self.trace_data.host.extend(program_spans.load(d))
                finally:
                    shutil.rmtree(d, ignore_errors=True)
                print(f"trace: written and read in "
                      f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
        finally:
            spans.disable()


def _run(args, seed: int, trace: bool, on: bool) -> tuple[SpanCell, dict]:
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = SpanCell(bench, args.workload, seed, args.seconds, trace)
    cell.program_spans = on
    cell.start()
    driver = importlib.import_module("lib." + cell.mix["kind"])
    return cell, driver.run(cell)


def traced(args) -> None:
    from lib import program_spans as ps, trace as tr
    bench = load_json(ROOT, "BENCHMARK.json")
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell, res = _run(args, seed, True, True)
        td = cell.trace_data
        e2e = {m["name"] for m in bench["end_to_end"]
               if metric_applies(m, cell.name, set())}
        metrics = {k: v["value"]
                   for k, v in per_layer(cell, res, e2e).items()}
        ok = [r for r in res["obs"]["records"] if r.ok]
        split = {k: ps.span_ms_per_request(td, name, len(ok))
                 for k, name in ps.SPLIT.items()}
        gap = ps.decode_host_gap_ms(td, res["obs"]["decode_module"])
        itl = [1e3 * g for r in ok for g in np.diff(r.emits)]
        covered = sum(split[k] or 0.0 for k in ps.KV_HOST)
        kv_host = metrics.get("kv_host_ms.chat")
        _out("traced", args.workload, {
            "seed": seed, "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "served": len(ok), "metrics": metrics, "split_ms": split,
            "split_share_of_kv_host": covered / kv_host if kv_host else None,
            "decode_host_gap_ms": gap,
            "itl_median_ms": float(np.median(itl)) if itl else None,
            "span_s": ps.span_s(td), "idle_gaps": tr.idle_gaps(td, k=40),
            "busy_s": tr.busy_s(td), "window_s": tr.window_s(td),
            "device": res["device"]})


def cost(args) -> None:
    for seed in [int(s) for s in args.seeds.split(",")]:
        for on in (False, True, True, False):
            _, res = _run(args, seed, False, on)
            _out("cost", args.workload, {
                "seed": seed, "spans": on, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"],
                "e2e": res["e2e"], "device": res["device"]})


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("traced", "cost"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1")
    args = ap.parse_args(argv)
    from lib.device import NoChipError
    try:
        {"traced": traced, "cost": cost}[args.what](args)
    except NoChipError as e:
        print(f"span_split: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
