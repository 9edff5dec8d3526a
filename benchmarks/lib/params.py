"""Parameter draws for statement templates.  Every draw comes from a numpy
Generator that the caller seeds from --seed, so a seed repeats its
parameters.  A domain is one JSON object with a `kind`:

  int       {"lo", "hi"}            whole number, both ends included
  choice    {"values": [...]}       one of the listed values
  date      {"lo", "hi"}            ISO day, both ends included
  key       {"table", "column"}     an existing key of the loaded data, by the
                                    traffic mix's key law (`KeyLaw`)
  fresh     {"base", "stride"}      base + client*stride + n: never used before
  affine    {"of", "mul", "add"}    mul * <earlier parameter> + add
  cents     {"of", "mod"}           (<earlier parameter> % mod) cents as "d.cc"
  format    {"template"}            str.format over the earlier parameters
"""

import numpy as np

from . import datagen


class KeyLaw:
    """Ranks by a Zipf law (theta as YCSB's zipfian constant; 0 = uniform),
    scattered over the key column by a seeded permutation so that the hot
    keys are not neighbours."""

    def __init__(self, keys, law, seed):
        n = len(keys)
        theta = float(law.get("theta", 0.0)) if law else 0.0
        w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), theta)
        self._cdf = np.cumsum(w)
        self._cdf /= self._cdf[-1]
        self._keys = np.asarray(keys)[
            np.random.default_rng([seed, 0x6b6579]).permutation(n)]

    def draw(self, rng, size):
        rank = np.searchsorted(self._cdf, rng.random(size), side="left")
        return self._keys[np.minimum(rank, len(self._keys) - 1)]


def draw(domains, rng, key_laws=None, client=0, n=0):
    """One value per domain, in the file's order (later ones may name
    earlier ones)."""
    out = {}
    for name, d in domains.items():
        kind = d["kind"]
        if kind == "int":
            out[name] = int(rng.integers(d["lo"], d["hi"] + 1))
        elif kind == "choice":
            out[name] = d["values"][int(rng.integers(0, len(d["values"])))]
        elif kind == "date":
            lo, hi = datagen.days(d["lo"]), datagen.days(d["hi"])
            out[name] = datagen.iso(int(rng.integers(lo, hi + 1)))
        elif kind == "key":
            law = key_laws[(d["table"], d["column"])]
            out[name] = int(law.draw(rng, 1)[0])
        elif kind == "fresh":
            out[name] = int(d["base"]) + client * int(d["stride"]) + n
        elif kind == "affine":
            out[name] = int(d["mul"]) * out[d["of"]] + int(d["add"])
        elif kind == "cents":
            c = out[d["of"]] % int(d["mod"])
            out[name] = f"{c // 100}.{c % 100:02d}"
        elif kind == "format":
            out[name] = d["template"].format(**out)
        else:
            raise ValueError(f"unknown parameter kind {kind!r} for {name!r}")
    return out
