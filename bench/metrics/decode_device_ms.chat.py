"""Model step: mean device ms of one execution of the jitted decode step
(`models/lm.py` `decode_fn`), from the trace."""

from lib import trace as tr


def read(obs):
    return tr.mean_module_ms(obs.get("trace"), obs.get("decode_module"))
