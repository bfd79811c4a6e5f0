"""Device: % of the traced chat window in which no module ran."""

from lib import trace as tr


def read(obs):
    return tr.idle_pct(obs.get("trace"))
