"""A cell of the real harness at a size the CPU runs in seconds: the
program's reduced configs, a chat mix scaled down to them, and no chip."""

from __future__ import annotations

import copy
import os

import jax

from run import Cell, load_json, BENCH

TINY_CONFIGS = {
    "qwen3-0.6b": dict(hidden_size=64, intermediate_size=128,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=2, head_dim=16, vocab_size=512),
    "mamba2-370m": dict(d_model=64, n_layer=2, d_state=16, headdim=16,
                        chunk_size=32),
}

TINY_MIX = dict(system_prompt_tokens=32, block_tokens=16, max_len=128,
                user_tokens={"median": 8, "sigma": 0.8, "min": 4, "max": 24},
                output_tokens={"min": 4, "max": 8})

# the reduced models' readings sit far below the full ones': a chat
# program's widest logit gap under 0.004 and its fp8 control's over 0.05
TINY_LIMITS = {"max_logit_gap": 0.02}


class TinyCell(Cell):
    def __init__(self, cell: str, seed: int = 7, seconds: float = 3.0,
                 rate: float = 4.0, trace: bool = False) -> None:
        bench = load_json(os.path.dirname(BENCH), "BENCHMARK.json")
        super().__init__(bench, cell, seed, seconds, trace)
        self.config = dict(self.config, **TINY_CONFIGS[self.config["name"]])
        self.vocab = self.config["vocab_size"]
        self.mix = dict(copy.deepcopy(self.mix), **TINY_MIX)
        self.cell = dict(self.cell, rate_rps=rate,
                         limits=TINY_LIMITS)

    def start(self) -> None:
        from lib.device import CompileMeter
        self.devs = jax.devices()[:1]
        self.peaks = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
        self.meter = CompileMeter()

    def arch(self):
        from repro.configs import get_reduced
        return get_reduced(self.config["program_arch"])

    def device_record(self) -> dict:
        return {"platform": "cpu", "kind": "cpu", "count": 1,
                "memory_peak_bytes": 0}
