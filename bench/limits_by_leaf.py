"""``calibrate.py limits`` for a cell whose fp8 control weights do not fit
on the chip beside its bf16 ones.

    python bench/limits_by_leaf.py --workload <cell> --seeds 1,2 --seconds <s>

``calibrate.py limits`` holds the bf16 weights and the control's (float32,
rounded to fp8) whole at once: for granite-4.0-h-small that is 4.8 GB and
9.7 GB of a 16 GB chip.  Here the control's weights are rounded leaf by
leaf from the bf16 tree, each bf16 leaf dropped as its copy is made; the
control's first choices are read with them alone; then the bf16 weights
are made again from the seed for both sets of gaps.  The control and the
decision are ``calibrate.py``'s: the same rounding, the same reference,
the run's own ``judge`` at the cell's committed limit.  It prints one JSON
line per seed, and appends it to the file ``calibrate.py limits`` writes.
"""

from __future__ import annotations

import argparse
import importlib
import sys

import numpy as np

from calibrate import _cell, _out


def control_weights(params):
    """`params` rounded as `check.fp8_weights` rounds them, leaf by leaf;
    the caller's tree is consumed, so pass the only reference to it."""
    import jax
    import jax.numpy as jnp
    from lib import check
    flat, tdef = jax.tree_util.tree_flatten_with_path(params)
    del params
    quant = jax.jit(check.fp8, static_argnums=1)
    leaves = []
    for i in range(len(flat)):
        path, a = flat[i]
        flat[i] = None
        if a.dtype == jnp.bfloat16:
            stacked = any(getattr(k, "key", None) == "periods" for k in path)
            a = quant(a, tuple(range(1, a.ndim)) if stacked else None)
        leaves.append(a)
        del a
    return jax.tree_util.tree_unflatten(tdef, leaves)


def control_items(cell, checker, items) -> list:
    """`items` with the fp8 control's first choice at each compared
    position in place of the program's token."""
    import jax
    import jax.numpy as jnp
    from lib import check
    mod, c = cell.config_module, cell.config
    pick = jax.jit(lambda p, t, r: jnp.argmax(
        mod.forward(c, p, t, check.fp8_rows)[r], -1))
    pq = control_weights(cell.make_params())
    out = []
    for it in items:
        toks, rows, _, n = checker._padded(it)
        out.append((it[0], it[1], np.asarray(pick(pq, toks, rows))[:n]))
    return out


def limits(args) -> None:
    from lib import check
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = _cell(args, seed)
        cell.start()
        runner = importlib.import_module("lib." + cell.mix["kind"])
        res = runner.run(cell)
        items = res["items"]
        checker = check.ServedCheck(cell.config_module, cell.config,
                                    cell.mix["max_len"],
                                    max(len(it[1]) for it in items))
        ctrl_items = control_items(cell, checker, items)
        params = cell.make_params()
        ctrl = checker.gaps(params, ctrl_items)
        prog = checker.gaps(params, items)
        del params
        limit = cell.cell["limits"]["max_logit_gap"]
        control_correct, _ = runner.judge(ctrl, 0, limit)
        _out("limits", args.workload, {
            "seed": seed, "attempted": res["attempted"],
            "failed": res["failed"], "served_tokens": int(prog.size),
            "program_max_gap": float(prog.max()),
            "program_gap_p99": float(np.percentile(prog, 99)),
            "control_max_gap": float(ctrl.max()),
            "control_gap_p50": float(np.percentile(ctrl, 50)),
            "control_changed_share": float(np.mean(ctrl > 0)),
            "limit": limit, "program_correct": res["correct"],
            "control_correct": control_correct,
            "e2e": res["e2e"], "device": res["device"]})


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="1,2,3")
    args = ap.parse_args(argv)
    from lib.device import NoChipError
    try:
        limits(args)
    except NoChipError as e:
        print(f"limits_by_leaf: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
