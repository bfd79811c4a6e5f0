"""Measurements that set a cell's numbers, each in one process on the chip.

    python bench/calibrate.py sweep  --workload <cell> --seed <n> --seconds <s> --rates 0.2,0.3
    python bench/calibrate.py limits --workload <cell> --seeds 1,2,3 --seconds <s>

``sweep`` serves the cell's traffic at each rate and prints the tails, so
the knee (the highest rate the system sustains) can be read off once; the
cell then offers a fixed rate below it.  ``limits`` runs the cell on each
seed and reads, on the requests each window served, the program's widest
logit gap and the fp8 control's: the two readings a check's limit is set
between.  The control's tokens then go through the run's own decision at
the cell's committed limit, which has to find them not correct
(``control_correct``).  Neither runs in the benchmark's own runs.  Each prints one JSON
line per rate or seed, and writes them to ``chiprun_out/`` as well.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

from run import Cell, ROOT, load_json


def _out(kind: str, workload: str, line: dict) -> None:
    print(json.dumps(line), flush=True)
    d = os.path.join(ROOT, "chiprun_out")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"calibrate.{kind}.{workload}.jsonl"),
              "a") as f:
        f.write(json.dumps(line) + "\n")


def _cell(args, seed: int, rate: float | None = None) -> Cell:
    cell = Cell(load_json(ROOT, "BENCHMARK.json"), args.workload, seed,
                args.seconds, False)
    if rate is not None:
        cell.cell = dict(cell.cell, rate_rps=rate)
    return cell


def sweep(args) -> None:
    for rate in [float(r) for r in args.rates.split(",")]:
        cell = _cell(args, args.seed, rate)
        cell.start()
        res = importlib.import_module("lib." + cell.mix["kind"]).run(cell)
        recs = [r for r in res["obs"]["records"] if r.ok]
        ttft = [1e3 * (r.emits[0] - r.due) for r in recs]
        waits = [r.start - r.due for r in recs]
        _out("sweep", args.workload, {
            "rate_rps": rate, "attempted": res["attempted"],
            "failed": res["failed"], "correct": res["correct"],
            "ttft_p50_ms": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95_ms": res["e2e"].get("ttft_p95_ms"),
            "itl_p95_ms": res["e2e"].get("itl_p95_ms"),
            "wait_first_half_s": float(np.mean(waits[:len(waits) // 2]))
            if len(waits) > 1 else None,
            "wait_second_half_s": float(np.mean(waits[len(waits) // 2:]))
            if waits else None,
            "service_s": float(np.mean([r.emits[-1] - r.start
                                        for r in recs])) if recs else None,
            "setup_s": res["e2e"]["setup_s"], "device": res["device"]})


def limits(args) -> None:
    from lib import check
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = _cell(args, seed)
        cell.start()
        driver = importlib.import_module("lib." + cell.mix["kind"])
        res = driver.run(cell)
        items = res["items"]
        checker = check.ServedCheck(cell.config_module, cell.config,
                                    cell.mix["max_len"],
                                    max(len(it[1]) for it in items))
        params = cell.make_params()
        ctrl = checker.control_gaps(params, items)
        prog = checker.gaps(params, items)
        del params
        limit = cell.cell["limits"]["max_logit_gap"]
        control_correct, _ = driver.judge(ctrl, 0, limit)
        _out("limits", args.workload, {
            "seed": seed, "attempted": res["attempted"],
            "failed": res["failed"], "served_tokens": int(prog.size),
            "program_max_gap": float(prog.max()) if prog.size else None,
            "program_gap_p99": float(np.percentile(prog, 99))
            if prog.size else None,
            "control_max_gap": float(ctrl.max()) if ctrl.size else None,
            "control_gap_p50": float(np.percentile(ctrl, 50))
            if ctrl.size else None,
            "control_changed_share": float(np.mean(ctrl > 0))
            if ctrl.size else None,
            "limit": limit, "program_correct": res["correct"],
            "control_correct": control_correct,
            "e2e": res["e2e"], "device": res["device"]})


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    from lib.device import NoChipError
    try:
        {"sweep": sweep, "limits": limits}[args.what](args)
    except NoChipError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
