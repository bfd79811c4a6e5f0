"""Engine queue: mean ms from a chat request's scheduled arrival to the
moment the engine starts it (`serving/engine.py` serves one at a time)."""


def read(obs):
    waits = [r.start - r.due for r in obs.get("records", ()) if r.ok]
    return 1e3 * sum(waits) / len(waits) if waits else None
