"""Keys, values and plans, drawn from the seed: the one general generator.

A traffic file (``bench/traffic/<name>.json``) holds only parameters::

    {"plan_ops": 4096,
     "mix": {"get": 0.5, "update": 0.5},
     "keys": {"distribution": "uniform"}}

``mix`` gives each op kind's share of every plan; a plan holds exactly
``round(share * plan_ops)`` ops of each kind (the rounding remainder goes
to the largest share), in an order drawn from the seed, so every seed
sends the same sizes in another order.  Targets are drawn over the loaded
keys, ``uniform`` or ``zipfian`` with ``theta`` (rank r drawn with weight
``(r+1)^-theta``; rank order is the loaded keys' random order).  An
UPDATE writes a fresh value drawn from the seed.

Op codes are the harness's own; ``bench/run.py`` maps them onto the
program's plan ops.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

GET, UPDATE, PUT = 0, 1, 2
OP_CODES: Dict[str, int] = {"get": GET, "update": UPDATE, "put": PUT}

# keys are 8-byte integers in [1, 2^63 - 1): 0 is the program's empty
# slot, and 2^63 - 1 is P-Masstree's high key
KEY_LO, KEY_HI = 1, (1 << 63) - 1
VALUE_LO, VALUE_HI = 1, 1 << 62


def seed_sequence(seed: int, stream: str) -> np.random.SeedSequence:
    """An independent stream per purpose (keys, load values, warm-up,
    window), so changing how one is used leaves the others as they were.
    Any integer seed, negative or wider than 64 bits, is accepted."""
    words = [seed % (1 << 64), seed // (1 << 64) % (1 << 64)]
    return np.random.SeedSequence(words + [ord(c) for c in stream])


def rng_for(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(seed_sequence(seed, stream))


def key_set(n: int, seed: int) -> np.ndarray:
    """The ``n`` loaded keys, drawn from ``seed``, in load order."""
    return make_keys(rng_for(seed, "keys"), n)


def make_keys(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` distinct keys, uniform over [KEY_LO, KEY_HI), in random order."""
    keys = np.unique(rng.integers(KEY_LO, KEY_HI, size=n + n // 64 + 64))
    while keys.size < n:  # collisions in 2^63 are all but impossible
        more = rng.integers(KEY_LO, KEY_HI, size=n)
        keys = np.unique(np.concatenate([keys, more]))
    return rng.permutation(keys)[:n].astype(np.int64)


def make_values(rng: np.random.Generator, n: int) -> np.ndarray:
    return rng.integers(VALUE_LO, VALUE_HI, size=n).astype(np.int64)


def op_counts(mix: Dict[str, float], plan_ops: int) -> np.ndarray:
    """Exact ops of each kind per plan, indexed by op code."""
    unknown = set(mix) - set(OP_CODES)
    if unknown:
        raise ValueError(f"unknown op kinds {sorted(unknown)} in mix")
    counts = np.zeros(len(OP_CODES), np.int64)
    for kind, share in mix.items():
        counts[OP_CODES[kind]] = int(round(share * plan_ops))
    largest = OP_CODES[max(mix, key=mix.get)]
    counts[largest] += plan_ops - int(counts.sum())
    if (counts < 0).any():
        raise ValueError(f"mix {mix} does not fit {plan_ops} ops")
    return counts


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    return np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -theta)


class Traffic:
    """Plans of one traffic mix over a fixed set of loaded keys."""

    def __init__(self, params: dict, keys: np.ndarray,
                 rng: np.random.Generator):
        self.plan_ops = int(params["plan_ops"])
        self.counts = op_counts(params["mix"], self.plan_ops)
        if self.counts[PUT]:
            raise ValueError("traffic PUTs of fresh keys are not supported: "
                             "targets are drawn over the loaded keys")
        self.keys = keys
        self.rng = rng
        dist = params.get("keys", {"distribution": "uniform"})
        kind = dist["distribution"]
        if kind == "uniform":
            self._cdf = None
        elif kind == "zipfian":
            self._cdf = zipf_cdf(keys.size, float(dist["theta"]))
        else:
            raise ValueError(f"unknown key distribution {kind!r}")

    def ranks(self, size: int) -> np.ndarray:
        if self._cdf is None:
            return self.rng.integers(0, self.keys.size, size=size)
        u = self.rng.random(size) * self._cdf[-1]
        r = np.searchsorted(self._cdf, u, side="right")
        return np.minimum(r, self.keys.size - 1)

    def next_plan(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(codes int8, keys int64, aux int64) of one plan; ``aux`` is
        the value an UPDATE writes, 0 for a GET."""
        codes = self.rng.permutation(
            np.repeat(np.arange(len(OP_CODES), dtype=np.int8), self.counts))
        keys = self.keys[self.ranks(self.plan_ops)]
        aux = np.where(codes == UPDATE,
                       make_values(self.rng, self.plan_ops), 0)
        return codes, keys, aux


__all__ = ["GET", "KEY_HI", "KEY_LO", "OP_CODES", "PUT",
           "Traffic", "UPDATE", "key_set", "make_keys", "make_values",
           "op_counts", "rng_for", "seed_sequence", "zipf_cdf"]
