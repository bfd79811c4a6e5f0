"""How `correct` is decided for a served model: served tokens against the
plain reference, and the control that must fail the same comparison.

For each sampled request the reference reads the prompt and the tokens the
program served, and at each served position gives the gap by which the
served token's logit lies below its best.  Greedy decoding picks the
reference's best up to rounding, so a sound program's widest gap is small;
a wrong cache, a stale restore or an altered token puts a token far down.

The control is the reference computed in fp8 (e4m3) where the
configuration states bf16, the step that would tempt a change: weights
rounded to fp8 with one scale per tensor of a layer, and the input of every
matmul rounded to fp8 with one scale per row, accumulating in float32.  At
the same positions it reads the gap of the token the fp8 model puts first.
The rounding is `lax.reduce_precision`, which XLA keeps: a round trip of
casts (float32 to float8 and back) inside one program is folded away on the
TPU, and reads as no rounding at all.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

E4M3_MAX = 240.0      # largest e4m3 value `reduce_precision` can hold


def fp8(x, axes=None):
    """`x` rounded to e4m3 after scaling its largest value (over `axes`)
    to the format's largest; returned in float32."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    sc = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return jax.lax.reduce_precision(x / sc, exponent_bits=4,
                                    mantissa_bits=3) * sc


def fp8_rows(x):
    """A matmul input rounded to fp8, one scale per row."""
    return fp8(x, axes=-1)


def fp8_weights(params):
    """`params` with every bf16 leaf rounded to fp8, one scale per tensor
    (per layer of a stacked leaf), in float32.  Other leaves are left as
    they are."""
    def quant(path, a):
        if a.dtype != jnp.bfloat16:
            return a
        stacked = any(getattr(k, "key", None) == "periods" for k in path)
        return fp8(a, axes=tuple(range(1, a.ndim)) if stacked else None)
    return jax.tree_util.tree_map_with_path(quant, params)


class ServedCheck:
    """Jitted reference comparisons at one padded length, so every item
    of a run shares one compiled program.  An item is ``(tokens,
    positions, targets)``: the sequence the reference reads, the positions
    whose logits are compared, and the program's token at each."""

    def __init__(self, cfg_mod, config: dict, pad_to: int, max_rows: int):
        self.pad_to, self.max_rows = pad_to, max_rows

        def logits(p, toks, pos, act=None):
            return cfg_mod.forward(config, p, toks, act)[pos]

        def gaps(p, toks, pos, tgt):
            lg = logits(p, toks, pos)
            return jnp.max(lg, -1) - jnp.take_along_axis(
                lg, tgt[:, None], -1)[:, 0]

        def control(p, pq, toks, pos):
            pick = jnp.argmax(logits(pq, toks, pos, fp8_rows), -1)
            return gaps(p, toks, pos, pick)

        self._gaps = jax.jit(gaps)
        self._control = jax.jit(control)
        self._quant = jax.jit(fp8_weights)

    def _padded(self, item):
        seq, pos, tgt = (np.asarray(x, np.int32) for x in item)
        n = pos.size
        assert seq.size <= self.pad_to and 0 < n <= self.max_rows
        toks = np.zeros(self.pad_to, np.int32)
        toks[:seq.size] = seq
        rows = np.full(self.max_rows, pos[-1], np.int32)
        rows[:n] = pos
        tgts = np.zeros(self.max_rows, np.int32)
        tgts[:n] = tgt
        return toks, rows, tgts, n

    def gaps(self, params, items) -> np.ndarray:
        """Gap of the program's token at every compared position."""
        out = []
        for item in items:
            toks, rows, tgts, n = self._padded(item)
            out.append(np.asarray(self._gaps(params, toks, rows, tgts))[:n])
        return np.concatenate(out) if out else np.zeros(0)

    def control_gaps(self, params, items) -> np.ndarray:
        """Gap of the fp8 control's first choice at the same positions."""
        pq = self._quant(params)
        out = []
        for item in items:
            toks, rows, _, n = self._padded(item)
            out.append(np.asarray(self._control(params, pq, toks, rows))[:n])
        return np.concatenate(out) if out else np.zeros(0)


def served_item(prompt, served) -> tuple:
    """A request's served tokens: the logits at position p predict token
    p + 1, so served token j is compared at position len(prompt) - 1 + j."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    pos = np.arange(served.size) + prompt.size - 1
    return np.concatenate([prompt, served]), pos, served


def pick_samples(finished: list, n_tokens: int, rng: np.random.Generator
                 ) -> list:
    """The longest finished request and others drawn from `rng` until the
    sample holds `n_tokens` served tokens (or every request).  Items are
    (prompt, served) pairs."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    chosen = [order[0]]
    rest = list(rng.permutation(order[1:]))
    total = len(finished[order[0]][1])
    while rest and total < n_tokens:
        i = int(rest.pop(0))
        chosen.append(i)
        total += len(finished[i][1])
    return [finished[i] for i in chosen]
