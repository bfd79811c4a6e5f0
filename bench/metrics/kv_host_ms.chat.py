"""KV store: mean host ms per chat request inside `KVCacheStore.lookup`,
`get` and `put` (device-to-host copy, serialisation, filesystem), from the
benchmark's wrapper around the program's store."""


def read(obs):
    kv = [r.kv_s for r in obs.get("records", ()) if r.ok]
    return 1e3 * sum(kv) / len(kv) if kv else None
