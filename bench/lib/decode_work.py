"""Work and device time of one decode call, averaged over a traced chat
window: what the decode roofline and MFU readers share."""

from __future__ import annotations

from . import trace as tr


def per_call(obs: dict) -> dict | None:
    """Mean operations, least bytes and device seconds of a decode call,
    or None where the trace holds no execution of the decode module.  The
    work is averaged over every call the window made (each at its own
    cache length), the time over every execution the trace holds."""
    t, name = obs.get("trace"), obs.get("decode_module")
    evs = tr.module_events(t, name) if t is not None and name else []
    calls = [n for r in obs.get("records", ()) for _, n in r.calls]
    if not evs or not calls:
        return None
    mod, c = obs["config_module"], obs["config"]
    return {"flops": sum(mod.decode_flops(c, n) for n in calls) / len(calls),
            "bytes": sum(mod.decode_bytes(c, n) for n in calls) / len(calls),
            "device_s": sum(d for _, _, d in evs) / len(evs) / 1e9}
