"""String predicates against a dictionary, resolved on the host.

A predicate over a TEXT column (LIKE, IN, a range, an equality over a
transformed column) is decided once a dictionary VALUE, not once a row:
the rows hold codes.  `resolve` gives the verdicts in the form a program
reads them: at most `UNROLL` matching codes as the codes themselves (the
program compares against each), more as a BITMAP over the dictionary's
codes, 32 codes an int32 word (`bit_of` reads bit `code`).  A compiled
tier hands the words to its program as an ARGUMENT (exec/mesh_exec.py), so
the program holds no constant of the dictionary's size and is not rebuilt
for another pattern's verdicts; where a program is built around its
dictionaries anyway (the fused tier, eager evaluation) the same words are
a constant.

The verdicts are cached by the dictionary's list and LENGTH: dictionaries
are append-only (storage/store.StringDict, the mesh tier's union lists),
so a list of the same length holds the same strings.  What the cache saves
is the pass over the values: TPC-H Q13's `o_comment not like
'%special%requests%'` meets 1.49 M distinct comments at SF1, 0.66 s of
`re.match` a pass on this sandbox's CPU (a vectorised `numpy.char.find`
over the same values took 5.8 s, 4.9 of them building the fixed-width
array; one regex over the joined text 1.9 s: PERF.md section 6, PR 39),
and a statement's program is traced once a size class.
"""

from __future__ import annotations

import collections
import itertools
import threading

import jax.numpy as jnp
import numpy as np

from ..plan import exprs as E
from ..storage.batch import size_class

#: a code set up to this size unrolls into compares inside the program
UNROLL = 16
#: kinds whose verdicts are those of the positive form, negated a row
NEGATED = ("ne", "not_like", "not_in")

_LOCK = threading.Lock()
_CACHE: collections.OrderedDict = collections.OrderedDict()  # guarded_by: _LOCK
_CACHE_MAX = 64


def column_of(pred) -> str:
    """The name of the dictionary-coded column a string predicate reads
    (under its TextExpr, if it has one)."""
    c = pred.col
    return c.col.name if isinstance(c, E.TextExpr) else c.name


def _verdicts(pred: E.StrPred, values, n: int) -> np.ndarray:
    """bool a value of the dictionary's first n: does the string satisfy
    the predicate's POSITIVE form (`ne`, `not_like`, `not_in` are negated
    by the reader)."""
    if pred.param is not None:
        # Executor._prep gives a run-time string its value before
        # compiling: reaching here would compile an empty pattern set,
        # a wrong answer
        raise E.ExprError(f"text parameter {pred.param[0]} is not bound")
    # a union dictionary may grow behind this pass (a tail staged by
    # another session): the first n are the ones the key names
    strings = itertools.islice(values, n)
    if isinstance(pred.col, E.TextExpr):
        strings = map(pred.col.apply, strings)
    k = pred.kind
    if k in ("eq", "ne", "in", "not_in"):
        hits = map(frozenset(pred.patterns).__contains__, strings)
    elif k in ("like", "not_like"):
        from .expr_compile import like_to_regex
        hits = (m is not None for m in
                map(like_to_regex(pred.patterns[0]).match, strings))
    elif k in ("lt", "le", "gt", "ge"):
        p = pred.patterns[0]
        hits = map({"lt": p.__gt__, "le": p.__ge__, "gt": p.__lt__,
                    "ge": p.__le__}[k], strings)
    else:
        raise E.ExprError(f"unknown string predicate {k}")
    return np.fromiter(hits, dtype=bool, count=n)


def resolve(pred: E.StrPred, values):
    """(codes, words) of `pred` against the dictionary `values` (a list,
    by code): the matching codes (int32) and None where there are at most
    UNROLL of them, else None and the verdicts as a bitmap: a bit a code
    in int32 words, least significant bit first, zeros to a size class."""
    n = len(values)
    key = (id(values), n, pred)
    with _LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            _CACHE.move_to_end(key)
            return hit[1], hit[2]
    table = _verdicts(pred, values, n)
    if int(table.sum()) <= UNROLL:
        codes, words = np.flatnonzero(table).astype(np.int32), None
    else:
        # to a size class of words, so that a program built around the
        # bitmap's shape serves dictionaries of about that many strings
        # (another seed's o_comment: the persistent cache's entry hits)
        bits = np.packbits(table, bitorder="little")
        bits = np.pad(bits, (0, 4 * size_class(-(-len(bits) // 4), floor=1)
                             - len(bits)))
        codes, words = None, bits.view("<u4").astype(np.uint32).view(np.int32)
    with _LOCK:
        # host values only (numpy, never a tracer), so a program being
        # traced may fill the memo; another thread may have meanwhile.
        # The list rides along: its id stays its own while the entry lives
        hit = _CACHE.get(key)
        if hit is None:
            hit = (values, codes, words)
            _CACHE[key] = hit  # otblint: disable=trace-purity
            while len(_CACHE) > _CACHE_MAX:
                _CACHE.popitem(last=False)  # otblint: disable=trace-purity
    return hit[1], hit[2]


def bit_of(codes, words):
    """bit `code` of the bitmap `words` a row; a code outside the bitmap
    (a padding row's) reads an entry inside it, which the row's validity
    masks."""
    c = jnp.clip(codes.astype(jnp.int32), 0, 32 * words.shape[0] - 1)
    w = words[c >> 5]
    return ((w >> (c & 31)) & 1) == 1
