"""KV store: prompt tokens restored from a stored prefix, in % of all prompt
tokens of the chat requests served (the engine's `reused_len` count)."""


def read(obs):
    ok = [r for r in obs.get("records", ()) if r.ok]
    total = sum(r.prompt_len for r in ok)
    return 100.0 * sum(r.reused for r in ok) / total if total else None
