"""Columnar codecs: compressed device residency for staged columns.

Reference analog: TOAST / varlena compression (src/backend/access/
common/toast_internals.c) — the reference compresses wide values so a
heap page holds more rows and the buffer cache goes further.  Here the
scarce cache is device HBM and the dominant cost is host->device
transfer (the PR-12 morsel bench made PCIe the critical path), so the
compression unit is the COLUMN: every staged device array carries the
narrowest integer representation its values provably fit, and the
executor computes on the codes — decode is an elementwise affine map /
LUT gather that XLA fuses into the consuming kernel, so most payload
columns never materialize decoded.

Three codec families, chosen per column at stage time from the actual
values, persisted like the join ladder (exec/plancache.py Ladder):

- pack (uint8/16/32): direct downcast, proven 0 <= v <= 2^w - 1.
  Zero-padding decodes to 0 exactly (matches raw staging).
- for (frame-of-reference, uint8/16/32): code = v - lo + 1 with the
  reference `lo` from the proven min.  Code 0 is RESERVED as the
  padding sentinel so zero-padded rows decode to exactly 0 — MVCC
  visibility (ops/kernels.py visibility_mask) depends on padded
  __xmax_ts staying 0.  The reference rides the staged dict as a
  shape-(1,) aux array (`__enc.for.<col>`, value lo - 1), a TRACED
  input: reference drift never recompiles.
- dict (uint8/16): append-only dictionary for low-cardinality ints —
  the TEXT union-dictionary scheme (storage/store.py StringDict)
  extended to integers.  Codes are index + 1; slot 0 of the LUT is the
  0 sentinel for padding.  The LUT is a pow2-capacity aux array
  (`__enc.dict.<col>`), traced, so append-only growth within capacity
  never changes a program.

Program-key discipline (analysis/cardinality.py codec-key rule): the
only encoding-derived value that may reach program-key material is the
quantized class token from codec_class() — family + width (+ pow2 LUT
capacity, or the proven code limit of a 32-bit class), e.g. "pack8",
"for16", "dict8/256", "pack32/6291456".  Widths are an enum, capacities
quantize through batch.lut_capacity and code limits through
batch.size_class, so the key domain stays bounded and otbcard's cardinality proof holds.  The
token is also what the host KNOWS about a key column's range when it
builds a program (span_bound).  Aux CONTENTS
(references, LUT values) are traced data, never key material.

The per-(table, column) descriptor ladder is process-global so every
holder of a table — primary store, HotStandby replica store, mesh
shards — encodes with one descriptor and dictionary codes stay valid
across replicas.  A value outside the proven range re-chooses the
descriptor (monotone widening), which is key-visible and costs one
bounded recompile, exactly like join-ladder growth.  Set
OTB_CODEC_STATE=<path> to persist the ladder to a JSON file across
processes (documented in README next to the join-ladder docs);
OTB_CODEC=0 disables encoding entirely (bit-identity escape hatch).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

import numpy as np

from ..utils import locks
from .batch import lut_capacity, size_class

#: staged-namespace prefix for codec aux arrays: FOR references and
#: dictionary LUTs ride the staged dict as traced program inputs — the
#: CLASS is program-key material, the aux contents never are.
ENC_PREFIX = "__enc."

_STATE_LOCK = locks.RLock("storage.codec._STATE_LOCK")
_WIDTHS = (8, 16, 32)
_DICT_SAMPLE = 1 << 16    # probe rows before an exact unique() pass
_DICT_MAX_CARD = 1 << 12  # beyond this, dictionary residency stops paying


@dataclasses.dataclass(frozen=True)
class Enc:
    """One column's encoding descriptor.  family/width/cap are the
    QUANTIZED key material (codec_class); `lo` and the dictionary
    values are data, shipped through traced aux arrays."""
    family: str   # "pack" | "for" | "dict"
    width: int    # 8 | 16 | 32 — code dtype is uint{width}
    orig: str     # original staged dtype str ("int64", "int32", ...)
    lo: int = 0   # for: reference (code = v - lo + 1; 0 = padding)
    cap: int = 0  # dict: pow2 LUT capacity incl the sentinel slot
    limit: int = 0  # pack32/for32: proven code limit (a size class);
    #                 0 = the width's 2**width

    @property
    def code_limit(self) -> int:
        """Every code of the column is below this."""
        return self.limit or 1 << self.width

    @property
    def code_dtype(self):
        return np.dtype(f"uint{self.width}")


class _ColState:
    """Ladder entry for one (table, column): the persisted descriptor
    plus append-only dictionary state.  guarded_by: _STATE_LOCK"""
    __slots__ = ("enc", "values", "index")

    def __init__(self, enc, values=None):
        self.enc = enc                    # Enc | None (None = raw pin)
        self.values = list(values or [])  # dict family: code-1 -> value
        self.index = {v: i + 1 for i, v in enumerate(self.values)}


#: (table, col) -> _ColState
_LADDER: dict = {}     # guarded_by: _STATE_LOCK
_STATE_LOADED = False  # guarded_by: _STATE_LOCK


def enabled() -> bool:
    """Codec escape hatch: OTB_CODEC=0 stages every column raw (the
    bit-identity A/B arm of tests/test_codec.py)."""
    return os.environ.get("OTB_CODEC", "1") != "0"


def eligible(name: str, h) -> bool:
    """Encodable staged arrays: 1-D integers wider than a byte — value
    columns, MVCC sys columns, TEXT dict codes.  Null masks (bool),
    floats and vector payloads stage raw."""
    return (not name.startswith(ENC_PREFIX)
            and h.ndim == 1 and h.dtype.kind in "iu"
            and h.dtype.itemsize > 1)


# -- quantized key material ---------------------------------------------
def codec_class(enc) -> str:
    """The quantized codec-class token — the ONLY encoding-derived
    value allowed into program-key material (the codec-key lint rule):
    family + width, plus the pow2 LUT capacity for dictionaries (the
    capacity is the aux array's shape, hence aval-visible, hence it
    must be key-visible; it is already quantized via lut_capacity)."""
    if enc is None:
        return "raw"
    if enc.family == "dict":
        return f"dict{enc.width}/{enc.cap}"
    if enc.limit:
        return f"{enc.family}{enc.width}/{enc.limit}"
    return f"{enc.family}{enc.width}"


_RANGED = re.compile(r"(?:pack|for)(\d+)(?:/(\d+))?")


def span_bound(cls) -> "int | None":
    """What a class token proves about the column's staged values: an
    upper bound on max - min (pack: 0 <= v < limit; for: codes
    1 .. limit - 1 above one reference), or None where the class
    proves no range (raw, dictionaries).  Host knowledge the executor
    may choose an algorithm by when it BUILDS a program (the join
    kernels' direct-address table and packed sort, ops/kernels.py): the
    token is program-key material, so a column that outgrows the bound
    re-chooses its descriptor and the program is built anew."""
    m = _RANGED.fullmatch(cls) if isinstance(cls, str) else None
    if m is None:
        return None
    width, limit = m.groups()
    return (int(limit) if limit else 1 << int(width)) - 1


def codec_classes(store) -> tuple:
    """The codec classes actually STAGED for this store, sorted —
    program-key material for the fused tier (exec/fused.py
    _table_sig).  Reads what note_staged recorded at staging time, not
    the live ladder, so key and traced avals can never disagree when
    another holder of the same table name promotes the ladder."""
    return tuple(sorted(getattr(store, "_otb_codec_classes", {}).items()))


def note_staged(store, encs: dict) -> None:
    """Record the classes staged for this store (bufferpool staging /
    morsel ensure_classes) — the source codec_classes() reads."""
    try:
        store._otb_codec_classes = {
            c: codec_class(e) for c, e in encs.items() if e is not None}
    except AttributeError:
        pass


def invalidate_ladder(table: str) -> None:
    """Drop a table's ladder entries (the DDL-drop invalidation edge:
    a re-created table must re-learn its descriptors, not inherit the
    dead table's value distribution)."""
    with _STATE_LOCK:
        for key in [k for k in _LADDER if k[0] == table]:
            del _LADDER[key]
        _save_locked()


# -- descriptor choice / validation -------------------------------------
def _range_width(span: int):
    """Narrowest enum width whose code space holds `span` values plus
    the padding sentinel."""
    for w in _WIDTHS:
        if span <= (1 << w) - 2:
            return w
    return None


def _proven_limit(max_code: int, width: int) -> int:
    """The proven code limit of a 32-bit class, or 0 where the width
    says it all: uint32 codes are the class of every key column past
    65,534 values (TPC-H custkey 150,000, orderkey 6,000,000), and
    2**32 bounds no table a join could address directly.  Quantized to
    batch.size_class's quarter steps (the ladder the staged tables'
    own padding rides: orderkey's 6,000,000 proves 6,291,456, the
    width lineitem pads to), so the key domain stays an enum and a
    growing column re-chooses at most four times per doubling."""
    if width != 32:
        return 0
    limit = size_class(max_code + 1, floor=1 << 16)
    return limit if limit < 1 << 32 else 0


def _fits_locked(st: _ColState, h) -> bool:
    """Do these values fit the persisted descriptor without widening?
    (Dictionaries may still extend append-only within capacity.)"""
    enc = st.enc
    if str(h.dtype) != enc.orig:
        return False
    if h.size == 0:
        return True
    vmin, vmax = int(h.min()), int(h.max())
    if enc.family == "pack":
        return vmin >= 0 and vmax <= enc.code_limit - 1
    if enc.family == "for":
        return vmin >= enc.lo and vmax - enc.lo <= enc.code_limit - 2
    u = np.unique(h)
    new = sum(1 for v in u if int(v) not in st.index)
    return len(st.values) + new + 1 <= enc.cap


def _choose_locked(h, prev=None, refine=True) -> _ColState:
    """Choose a descriptor from the actual values.  `prev` is the
    outgrown state, if any — an outgrown DICTIONARY extends its
    append-only value list into a larger capacity (codes already
    resident elsewhere stay valid) instead of rebuilding.  `refine`
    asks for a 32-bit class's proven code limit (_proven_limit): not for
    the MVCC system columns, which no join keys on and whose ids and
    timestamps only ever grow."""
    orig = str(h.dtype)
    if h.size == 0:
        # nothing provable yet: stage raw WITHOUT pinning, so the
        # first real load still gets to choose
        return _ColState(None)
    vmin, vmax = int(h.min()), int(h.max())
    itemsize = h.dtype.itemsize

    if prev is not None and prev.enc is not None \
            and prev.enc.family == "dict":
        u = np.unique(h)
        new = [int(v) for v in u if int(v) not in prev.index]
        nvals = len(prev.values) + len(new)
        if nvals <= _DICT_MAX_CARD:
            cap, width = _dict_geometry(nvals)
            if width is not None and width // 8 < itemsize:
                st = _ColState(
                    Enc("dict", width, orig, cap=cap), prev.values)
                for v in new:
                    st.index[v] = len(st.values) + 1
                    st.values.append(v)
                return st

    pack_w = _range_width(vmax) if vmin >= 0 else None
    for_w = None
    drifts = False
    if vmin > np.iinfo(h.dtype).min:  # lo - 1 must be representable
        for_w = _range_width(vmax - vmin)
        if for_w is not None and vmin >= (1 << 40):
            # wall-clock-scale reference (MVCC timestamps): appends
            # drift forward forever, so a width proven on today's span
            # would promote on every batch — start at 32 bits (still
            # 2x narrower than the int64 original), all of them
            for_w = max(for_w, 32)
            drifts = True
    best = None
    for fam, w in (("pack", pack_w), ("for", for_w)):
        if w is not None and w // 8 < itemsize \
                and (best is None or w < best[1]):
            best = (fam, w)

    if best is None or best[1] > 8:
        st = _dict_choose(h, itemsize, orig,
                          best[1] if best else 8 * itemsize)
        if st is not None:
            return st
    if best is None:
        return _ColState(None)
    fam, w = best
    lo = vmin if fam == "for" else 0
    limit = _proven_limit(vmax - lo + (fam == "for"), w) \
        if refine and not (drifts and fam == "for") else 0
    return _ColState(Enc(fam, w, orig, lo=lo, limit=limit))


def _dict_geometry(nvals: int):
    """(cap, width) for a dictionary of `nvals` values: pow2 capacity
    with headroom, clamped to the width's code space."""
    width = 8 if nvals + 1 <= (1 << 8) else 16
    if nvals + 1 > (1 << 16):
        return 0, None
    cap = min(lut_capacity(nvals + 1 + (nvals >> 2) + 1), 1 << width)
    return cap, width


def _dict_choose(h, itemsize: int, orig: str, beat_width: int):
    """Try the dictionary family: cheap sample probe first, exact
    unique() only when the sample looks low-cardinality."""
    sample = h if h.size <= _DICT_SAMPLE \
        else h[::max(1, h.size // _DICT_SAMPLE)]
    if np.unique(sample).size > _DICT_MAX_CARD:
        return None
    u = np.unique(h)
    if u.size > _DICT_MAX_CARD:
        return None
    cap, width = _dict_geometry(int(u.size))
    if width is None or width >= beat_width or width // 8 >= itemsize:
        return None
    return _ColState(Enc("dict", width, orig, cap=cap),
                     [int(v) for v in u])


# -- encode --------------------------------------------------------------
def _encode_locked(st: _ColState, h):
    """Encode under the existing descriptor, or None on a range/dtype
    violation.  Dictionary encode extends the append-only LUT within
    capacity (the caller re-uploads the aux array afterwards)."""
    enc = st.enc
    if str(h.dtype) != enc.orig:
        return None
    if h.size == 0:
        return np.zeros(0, enc.code_dtype)
    vmin, vmax = int(h.min()), int(h.max())
    if enc.family == "pack":
        if vmin < 0 or vmax > enc.code_limit - 1:
            return None
        return h.astype(enc.code_dtype)
    if enc.family == "for":
        if vmin < enc.lo or vmax - enc.lo > enc.code_limit - 2:
            return None
        return (h.astype(np.int64)
                - np.int64(enc.lo - 1)).astype(enc.code_dtype)
    u, inv = np.unique(h, return_inverse=True)
    new = [int(v) for v in u if int(v) not in st.index]
    if len(st.values) + len(new) + 1 > enc.cap:
        return None
    changed = bool(new)
    for v in new:
        st.index[v] = len(st.values) + 1
        st.values.append(v)
    if changed:
        _save_locked()
    ucodes = np.asarray([st.index[int(v)] for v in u],
                        dtype=enc.code_dtype)
    return ucodes[np.asarray(inv)]


def encode_staged(table: str, name: str, h):
    """Validate-or-choose the persisted descriptor for this column
    against the full staged values and encode.  Returns
    (codes, enc, aux_host) or None to stage raw.  A misfit (append
    drifted out of the proven range) re-chooses and persists — a
    key-visible, bounded recompile, exactly like join-ladder growth."""
    if not enabled() or not eligible(name, h):
        return None
    h = np.ascontiguousarray(h)
    with _STATE_LOCK:
        _load_locked()
        key = (table, name)
        st = _LADDER.get(key)
        if st is not None and st.enc is None:
            return None               # proven-raw pin: stays raw
        codes = _encode_locked(st, h) if st is not None else None
        if codes is None:
            st = _choose_locked(h, prev=st,
                                refine=not name.startswith("__"))
            _LADDER[key] = st
            _save_locked()
            if st.enc is None:
                return None
            codes = _encode_locked(st, h)
            assert codes is not None, (table, name, st.enc)
        return codes, st.enc, _aux_locked(st)


def encode_tail(table: str, name: str, enc: Enc, t):
    """Encode an append tail under an entry's EXISTING descriptor —
    never chooses or promotes.  Returns codes, or None when the tail
    drifted out of range (or the ladder moved past the entry): the
    caller falls back to a full restage.  Dictionary tails may extend
    the append-only LUT within capacity; the caller re-uploads the aux
    array (aux_host) after a successful tail encode."""
    with _STATE_LOCK:
        st = _LADDER.get((table, name))
        if st is None or st.enc != enc:
            return None
        return _encode_locked(st, np.ascontiguousarray(t))


def encode_window(table: str, name: str, h):
    """Encode one morsel window under the ladder descriptor ensured at
    stream start (ensure_classes) — validate-only, never chooses, so
    every chunk of a stream provably shares ONE program class.
    Returns (codes, enc, aux_host) or None (stage raw)."""
    if not enabled() or not eligible(name, h):
        return None
    with _STATE_LOCK:
        st = _LADDER.get((table, name))
        if st is None or st.enc is None:
            return None
        codes = _encode_locked(st, np.ascontiguousarray(h))
        if codes is None:
            return None
        return codes, st.enc, _aux_locked(st)


def ensure_classes(store, host_cols: dict) -> dict:
    """Stream-start ensure: validate-or-choose descriptors for every
    eligible staged column from the FULL host values, so each window
    of the stream (encode_window) fits one descriptor and the chunk
    programs never fork classes mid-stream.  Records the result on the
    store for codec_classes (program-key material).  Returns
    {col: Enc} for the encoded columns."""
    from ..utils.dtypes import stage_cast
    table = store.td.name
    encs: dict = {}
    if enabled():
        with _STATE_LOCK:
            _load_locked()
            for name in sorted(host_cols):
                h = stage_cast(np.asarray(host_cols[name]))
                if not eligible(name, h):
                    continue
                key = (table, name)
                st = _LADDER.get(key)
                if st is None or (st.enc is not None
                                  and not _fits_locked(st, h)):
                    st = _choose_locked(h, prev=st,
                                        refine=not name.startswith("__"))
                    _LADDER[key] = st
                    _save_locked()
                if st.enc is not None:
                    encs[name] = st.enc
    note_staged(store, encs)
    return encs


# -- aux arrays ----------------------------------------------------------
def aux_name(name: str, enc: Enc) -> str:
    """Staged-dict key of a column's aux array; the FAMILY rides the
    name so a staged dict is self-describing (enc_names)."""
    return f"{ENC_PREFIX}{enc.family}.{name}"


def _aux_locked(st: _ColState) -> np.ndarray:
    enc = st.enc
    od = np.dtype(enc.orig)
    if enc.family == "pack":
        # dtype marker only: decode target dtype = aux dtype
        return np.zeros(1, od)
    if enc.family == "for":
        return np.asarray([enc.lo - 1], od)
    lut = np.zeros(enc.cap, od)
    if st.values:
        lut[1:1 + len(st.values)] = np.asarray(st.values, od)
    return lut


def aux_host(table: str, name: str, enc: Enc):
    """Current host aux array for an encoded column (fresh LUT after a
    tail-extend), or None if the ladder moved past `enc`."""
    with _STATE_LOCK:
        st = _LADDER.get((table, name))
        if st is None or st.enc != enc:
            return None
        return _aux_locked(st)


# -- staged-dict introspection ------------------------------------------
def enc_names(arrs: dict) -> dict:
    """{col: aux_key} for every encoded column of a staged dict."""
    out = {}
    for k in arrs:
        if k.startswith(ENC_PREFIX):
            _fam, col = k[len(ENC_PREFIX):].split(".", 1)
            out[col] = k
    return out


def family_of(aux_key: str) -> str:
    return aux_key[len(ENC_PREFIX):].split(".", 1)[0]


def padded_of(arrs: dict) -> int:
    """Padded row count of a staged dict, skipping aux arrays (aux
    shapes are (1,) / (cap,), not the padded row geometry)."""
    for k, a in arrs.items():
        if not k.startswith(ENC_PREFIX):
            return int(a.shape[0])
    return 0


def logical_nbytes(arrs: dict) -> int:
    """Bytes this staged dict would occupy UNENCODED (original
    dtypes) — the numerator of otb_buffercache's effective-cache
    ratio (bytes_logical / bytes_resident)."""
    aux = enc_names(arrs)
    total = 0
    for k, a in arrs.items():
        if k.startswith(ENC_PREFIX):
            continue
        if k in aux:
            n = 1
            for d in a.shape:
                n *= int(d)
            total += n * int(np.dtype(arrs[aux[k]].dtype).itemsize)
        else:
            total += int(a.nbytes)
    return total


# -- ladder persistence --------------------------------------------------
def _state_path():
    return os.environ.get("OTB_CODEC_STATE") or None


def _load_locked():  # holds: _STATE_LOCK
    global _STATE_LOADED
    if _STATE_LOADED:
        return
    _STATE_LOADED = True
    path = _state_path()
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return
    for d in data:
        key = (d["table"], d["col"])
        if d["family"] == "raw":
            _LADDER[key] = _ColState(None)
        else:
            enc = Enc(d["family"], int(d["width"]), d["orig"],
                      lo=int(d.get("lo", 0)), cap=int(d.get("cap", 0)),
                      limit=int(d.get("limit", 0)))
            _LADDER[key] = _ColState(enc, d.get("values"))


def _save_locked():
    path = _state_path()
    if not path:
        return
    out = []
    for (table, col), st in sorted(_LADDER.items()):
        d = {"table": table, "col": col}
        if st.enc is None:
            d["family"] = "raw"
        else:
            d.update(family=st.enc.family, width=st.enc.width,
                     orig=st.enc.orig, lo=st.enc.lo, cap=st.enc.cap,
                     limit=st.enc.limit)
            if st.enc.family == "dict":
                d["values"] = list(st.values)
        out.append(d)
    tmp = f"{path}.tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, path)
    except OSError:
        pass


def ladder_snapshot() -> list:
    """(table, col, class) rows — obs / tests."""
    with _STATE_LOCK:
        return [(t, c, codec_class(st.enc))
                for (t, c), st in sorted(_LADDER.items())]


def reset_state():
    """Drop the descriptor ladder (isolation between tests)."""
    global _STATE_LOADED
    with _STATE_LOCK:
        _LADDER.clear()
        _STATE_LOADED = False
