import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without real hardware:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
        --shape train_4k [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Per cell it records (reports/dryrun/<arch>__<shape>__<mesh>.json):

* memory_analysis()  — per-device argument/output/temp bytes (fits HBM?);
* cost_analysis()    — HLO FLOPs / bytes accessed (roofline numerators);
* collective bytes   — summed operand sizes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute parsed from the
  post-SPMD compiled HLO (cost_analysis does not expose these).

NOTE the XLA_FLAGS line above MUST precede any jax import — jax locks the
device count at first init.  Do not set it globally: smoke tests and
benches must see one device.
"""

import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, SHAPES, get_config
from ..models import build_model
from ..optim import AdamWConfig
from ..train.steps import make_decode_step, make_train_step
from .mesh import make_production_mesh
from .specs import (abstract_state, input_specs, shardings_for_batch,
                    shardings_for_decode, shardings_for_state)
from ..parallel import default_rules

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# per-arch launch tuning for the train shape (found in §Perf iteration;
# accumulation bounds activation memory, EP fits jamba's 16 experts to the
# 16-way model axis exactly)
TRAIN_TUNING: dict[str, dict] = {
    "jamba-v0.1-52b": {"accum_steps": 8, "expert_partition": "expert"},
    "qwen2.5-14b": {"accum_steps": 2},
    "granite-8b": {"accum_steps": 2},
    "olmoe-1b-7b": {"accum_steps": 4},
    "qwen2-moe-a2.7b": {"accum_steps": 2},
    "h2o-danube-3-4b": {"accum_steps": 2},
    "whisper-tiny": {"accum_steps": 16},
}


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output-shape bytes of every collective op in post-SPMD HLO."""
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("%") or ls.startswith("ROOT"):
            body = ls.split(" = ", 1)
            if len(body) != 2:
                continue
            rhs = body[1]
            op = None
            for c in _COLLECTIVES:
                # match "... all-reduce(" or "all-reduce-start("
                if re.search(rf"\b{c}(-start)?\(", rhs):
                    op = c
                    break
            if op is None:
                continue
            # output shape(s): leading "f32[a,b]" possibly tuple "(f32[..)"
            nbytes = 0
            head = rhs.split(op)[0]
            for m in _SHAPE_RE.finditer(head):
                dt, dims = m.group(1), m.group(2)
                if dt not in _DTYPE_BYTES:
                    continue
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                nbytes += n * _DTYPE_BYTES[dt]
            out[op] += nbytes
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               expert_partition: str = "ff", remat: str | None = None,
               scan_layers: bool | None = None, accum_steps: int = 1):
    cfg = get_config(arch)
    if remat is not None:
        cfg = cfg.with_(remat=remat)
    if scan_layers is not None:
        cfg = cfg.with_(scan_layers=scan_layers)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.supports_long_context():
        return None  # skipped per DESIGN.md §Arch-applicability
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = default_rules(mesh, expert_partition=expert_partition)
    from ..parallel import ctx
    ctx.set_from_mesh(mesh, rules)
    specs = input_specs(cfg, shape, model)

    max_seq = shape.seq_len
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state, spec = abstract_state(model, max_seq, with_opt=True)
            state_sh = shardings_for_state(state, spec, mesh, rules)
            batch_sh = shardings_for_batch(specs, mesh, rules)
            step = make_train_step(model, AdamWConfig(),
                                   grad_shardings=state_sh.opt["m"],
                                   accum_steps=accum_steps)
            lowered = jax.jit(
                step,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
            ).lower(state, specs)
        elif shape.kind == "prefill":
            params, spec = abstract_state(model, max_seq, with_opt=False)
            from ..parallel import param_shardings
            p_sh = param_shardings(spec, params, mesh, rules)
            batch_sh = shardings_for_batch(specs, mesh, rules)
            lowered = jax.jit(
                lambda p, b: model.prefill(p, b),
                in_shardings=(p_sh, batch_sh),
            ).lower(params, specs)
        else:  # decode
            params, spec = abstract_state(model, max_seq, with_opt=False)
            from ..parallel import param_shardings
            p_sh = param_shardings(spec, params, mesh, rules)
            io_sh = shardings_for_decode(specs, mesh, rules)
            step = make_decode_step(model)
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, io_sh["token"], io_sh["cache"],
                              io_sh["cache_len"]),
                out_shardings=(None, io_sh["cache"]),
            ).lower(params, specs["token"], specs["cache"],
                    specs["cache_len"])
    return lowered, cfg, shape, mesh


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save: bool = True, **kw) -> dict | None:
    t0 = time.time()
    if SHAPES[shape_name].kind == "train":
        for k, v in TRAIN_TUNING.get(arch, {}).items():
            kw.setdefault(k, v)
    out = lower_cell(arch, shape_name, multi_pod, **kw)
    if out is None:
        print(f"SKIP  {arch} × {shape_name} (full attention at 500k)")
        return None
    lowered, cfg, shape, mesh = out
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    n_dev = mesh.devices.size
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "compile_s": round(t_compile, 1),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
        "per_device": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "code_bytes": int(mem.generated_code_size_in_bytes),
            "flops": flops,
            "bytes_accessed": bytes_acc,
            "collective_bytes": coll,
        },
    }
    if save:
        os.makedirs(REPORT_DIR, exist_ok=True)
        path = os.path.join(
            REPORT_DIR, f"{arch}__{shape_name}__{rec['mesh']}.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    gb = 1 << 30
    print(f"OK    {arch} × {shape_name} × {rec['mesh']}  "
          f"compile={t_compile:6.1f}s  "
          f"args={mem.argument_size_in_bytes / gb:7.2f}GiB/dev  "
          f"temp={mem.temp_size_in_bytes / gb:7.2f}GiB/dev  "
          f"flops/dev={flops:.3e}  coll={coll['total'] / gb:.3f}GiB")
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--expert-partition", default="ff",
                    choices=("ff", "expert"))
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    cells: list[tuple[str, str]] = []
    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape, mp,
                         expert_partition=args.expert_partition)
            except Exception as e:  # noqa: BLE001 - report and continue
                failures.append((arch, shape, mp, repr(e)[:200]))
                print(f"FAIL  {arch} × {shape} × "
                      f"{'2x16x16' if mp else '16x16'}: {e!r}"[:300])
    if failures:
        print(f"\n{len(failures)} failures")
        return 1
    print("\nALL CELLS PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
