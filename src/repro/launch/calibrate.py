import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""FLOPs/bytes/collective calibration for scanned models.

XLA's cost_analysis counts a while-loop body ONCE, so the scanned dry-run
underreports per-step cost by ~n_periods×.  We recover the true totals by
lowering the model UNROLLED at depths of exactly 1 and 2 periods:

    F(k) = f_outside + k·f_body   ⇒   f_body = F(2) − F(1)

and correcting the full-depth record:

    corrected = F(1) + (n_periods − 1)·f_body

applied to flops, bytes-accessed and per-collective bytes alike.  Writes
reports/calibration/<arch>__<shape>.json; benchmarks.roofline consumes them.

    PYTHONPATH=src python -m repro.launch.calibrate --all
"""

import argparse
import json
import sys

from ..configs import ARCH_IDS, SHAPES, get_config
from ..models.lm import n_periods, period_length
from .dryrun import collective_bytes

CAL_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "reports", "calibration")


def _measure(arch: str, shape_name: str, k_periods: int) -> dict | None:
    cfg = get_config(arch)
    plen = period_length(cfg) if cfg.family != "audio" else 1
    depth = k_periods * plen
    kw = {}
    if cfg.family == "audio":
        # scale encoder and decoder together
        cfg_small = cfg.with_(n_layers=depth, enc_layers=depth)
    else:
        cfg_small = cfg.with_(n_layers=depth)
    # monkey-patch the registry entry via direct lowering on the small cfg
    from ..models import build_model
    from .dryrun import make_production_mesh
    from .specs import (abstract_state, input_specs, shardings_for_batch,
                        shardings_for_decode, shardings_for_state)
    from ..parallel import default_rules, param_shardings
    from ..optim import AdamWConfig
    from ..train.steps import make_decode_step, make_train_step
    import jax

    cfg_small = cfg_small.with_(scan_layers=False)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.supports_long_context():
        return None
    model = build_model(cfg_small)
    mesh = make_production_mesh(multi_pod=False)
    rules = default_rules(mesh)
    from ..parallel import ctx
    ctx.set_from_mesh(mesh, rules)
    specs = input_specs(cfg_small, shape, model)
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state, spec = abstract_state(model, shape.seq_len, with_opt=True)
            state_sh = shardings_for_state(state, spec, mesh, rules)
            batch_sh = shardings_for_batch(specs, mesh, rules)
            step = make_train_step(model, AdamWConfig(),
                                   grad_shardings=state_sh.opt["m"])
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh, None)
                              ).lower(state, specs)
        elif shape.kind == "prefill":
            params, spec = abstract_state(model, shape.seq_len,
                                          with_opt=False)
            p_sh = param_shardings(spec, params, mesh, rules)
            batch_sh = shardings_for_batch(specs, mesh, rules)
            lowered = jax.jit(lambda p, b: model.prefill(p, b),
                              in_shardings=(p_sh, batch_sh)
                              ).lower(params, specs)
        else:
            params, spec = abstract_state(model, shape.seq_len,
                                          with_opt=False)
            p_sh = param_shardings(spec, params, mesh, rules)
            io_sh = shardings_for_decode(specs, mesh, rules)
            step = make_decode_step(model)
            lowered = jax.jit(step,
                              in_shardings=(p_sh, io_sh["token"],
                                            io_sh["cache"],
                                            io_sh["cache_len"]),
                              out_shardings=(None, io_sh["cache"]),
                              ).lower(params, specs["token"],
                                      specs["cache"], specs["cache_len"])
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "collective": coll}


def calibrate(arch: str, shape_name: str) -> dict | None:
    cfg = get_config(arch)
    if SHAPES[shape_name].name == "long_500k" \
            and not cfg.supports_long_context():
        return None
    nper = n_periods(cfg) if cfg.family != "audio" else cfg.n_layers
    f1 = _measure(arch, shape_name, 1)
    f2 = _measure(arch, shape_name, 2)
    if f1 is None or f2 is None:
        return None
    body = {
        "flops": f2["flops"] - f1["flops"],
        "bytes": f2["bytes"] - f1["bytes"],
        "collective": {k: f2["collective"][k] - f1["collective"][k]
                       for k in f1["collective"]},
    }
    corrected = {
        "flops": f1["flops"] + (nper - 1) * body["flops"],
        "bytes": f1["bytes"] + (nper - 1) * body["bytes"],
        "collective": {k: f1["collective"][k]
                       + (nper - 1) * body["collective"][k]
                       for k in f1["collective"]},
    }
    rec = {"arch": arch, "shape": shape_name, "n_periods": nper,
           "one_period": f1, "body": body, "corrected": corrected}
    os.makedirs(CAL_DIR, exist_ok=True)
    with open(os.path.join(CAL_DIR, f"{arch}__{shape_name}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args()
    cells = [(a, s) for a in ARCH_IDS for s in SHAPES] if args.all \
        else [(args.arch, args.shape)]
    fails = 0
    for arch, shape in cells:
        try:
            rec = calibrate(arch, shape)
            if rec is None:
                print(f"SKIP {arch} × {shape}")
                continue
            print(f"OK   {arch} × {shape}  corrected flops/dev = "
                  f"{rec['corrected']['flops']:.3e}  coll/dev = "
                  f"{rec['corrected']['collective']['total'] / 2**30:.2f} GiB")
        except Exception as e:  # noqa: BLE001
            fails += 1
            print(f"FAIL {arch} × {shape}: {e!r}"[:200])
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
