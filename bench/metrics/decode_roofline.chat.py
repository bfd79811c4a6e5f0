"""Kernels of the decode step: the least time the step needs (the larger of
its operations over peak FLOP/s and its bytes over peak HBM bytes/s, both
computed from the shapes by the configuration's file), in % of its device
time in the trace.  Bytes bound it at batch 1."""

from lib import decode_work


def read(obs):
    w = decode_work.per_call(obs)
    if w is None:
        return None
    least = max(w["flops"] / obs["peaks"]["bf16_flops"],
                w["bytes"] / obs["peaks"]["hbm_bytes_s"])
    return 100.0 * least / w["device_s"]
