"""CPU rehearsal of `chip_smoke.py`: its phase functions at reduced sizes,
with the Pallas kernels in interpret mode, and its checks shown to fail on
the faults they exist to catch."""

import importlib.util
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_reduced
from repro.serving import ServingEngine

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py")
cs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

QWEN = get_reduced("qwen3-0.6b")
SERVE = dict(max_new=4, max_len=64, reuse_prompt_len=20)
TRAIN = dict(steps=2, batch=2, seq=32)


def test_serve_phase_rehearsal(workdir):
    out = cs.serve_phase(QWEN, workdir, **SERVE)
    assert out["param_bytes"] > 0
    assert out["reused_len"] > 0
    assert out["logits_rel_l2"] <= cs.LOGITS_TOL


def test_serve_phase_catches_reuse_changing_tokens(workdir, monkeypatch):
    real = ServingEngine.generate_with_reuse

    def drifting(self, prompt, max_new=8, **kw):
        toks, info = real(self, prompt, max_new=max_new, **kw)
        if info["reused_len"] > 0:
            toks = [(toks[0] + 1) % QWEN.vocab] + list(toks[1:])
        return toks, info

    monkeypatch.setattr(ServingEngine, "generate_with_reuse", drifting)
    with pytest.raises(cs.SmokeCheckError, match="reuse changed tokens"):
        cs.serve_phase(QWEN, workdir, **SERVE)


def test_train_phase_rehearsal(workdir):
    out = cs.train_phase(QWEN, workdir, **TRAIN)
    assert np.isfinite(out["loss"])


def test_train_phase_catches_a_restore_that_differs(workdir, monkeypatch):
    real = CheckpointManager.restore

    def one_bit_off(self, step, like=None):
        tree = real(self, step, like=like)
        leaves, treedef = jax.tree.flatten(tree)
        bad = np.array(leaves[0])
        bad.view(np.uint8).reshape(-1)[0] ^= 1
        return jax.tree.unflatten(treedef, [bad] + leaves[1:])

    monkeypatch.setattr(CheckpointManager, "restore", one_bit_off)
    with pytest.raises(cs.SmokeCheckError, match="restored checkpoint"):
        cs.train_phase(QWEN, workdir, **TRAIN)


def test_kernel_phase_rehearsal_in_interpret_mode():
    out = cs.kernel_phase(QWEN, get_reduced("mamba2-370m"),
                          impl="pallas_interpret", seqs=(64, 20),
                          ssm_seq=64)
    assert set(out) == {"flash_s64_max_abs_diff", "flash_s20_max_abs_diff",
                        "ssd_y_max_abs_diff"}
    assert all(np.isfinite(v) for v in out.values())


@pytest.mark.parametrize("change", ["value", "dtype", "leaf_count"])
def test_same_bytes_rejects(change):
    a = {"w": np.arange(6, dtype=np.float32), "b": np.ones(2, np.int32)}
    b = {k: v.copy() for k, v in a.items()}
    assert cs.same_bytes(a, b)
    if change == "value":
        b["w"][3] = np.nextafter(b["w"][3], np.float32(10))
    elif change == "dtype":
        b["b"] = b["b"].astype(np.uint32)
    else:
        b["extra"] = np.zeros(1)
    assert not cs.same_bytes(a, b)


def test_main_refuses_to_run_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main() != 0
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    assert out.out == ""
