"""Whole decode step: its model operations over its device time, in % of
the chip's peak bf16 FLOP/s."""

from lib import decode_work


def read(obs):
    w = decode_work.per_call(obs)
    if w is None:
        return None
    return 100.0 * w["flops"] / (w["device_s"] * obs["peaks"]["bf16_flops"])
