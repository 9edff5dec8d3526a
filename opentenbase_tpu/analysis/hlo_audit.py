"""StableHLO program audit — otblint rules over exported MLIR.

Extends utils/lowering_check.py's f64 scan into the shared rule/report
machinery: every exported kernel and live fused/mesh program is scanned
for

- ``hlo-f64``            — f64 tensor types (no native TPU support);
- ``hlo-host-transfer``  — genuine host round-trips: send/recv,
  infeed/outfeed, host callbacks.  (``custom_call @Sharding`` is the
  partitioner's layout annotation, not a transfer, and is not flagged);
- ``hlo-dynamic-shape``  — dynamic-shape ops / ``?``-dim tensor types,
  which break AOT compilation caching on TPU;
- ``hlo-scatter-sort``   — a scatter or a sort in a kernel that declares
  itself free of both (``export_check(..., no_scatter_sort=True)``:
  finalize's live-row selection runs in front of every wide read, and a
  scatter with one update per input row costs more than the copy it
  saves; the dense aggregate is a compare-and-reduce because such a
  scatter serialises into its few cells);
- ``hlo-conditional``    — a ``case``/``if`` in a kernel that declares
  itself free of them (``export_check(..., no_conditional=True)``: the
  join kernels choose their algorithm when the program is built, so a
  program's device time is a function of its shapes and not of which
  arm the data took, and only one arm is compiled);
- ``hlo-wide-gather``    — a gather whose table or indices are i64 in a
  kernel that declares itself free of them (``export_check(...,
  no_wide_gather=True)``: the chip has no 64-bit lanes, so such a gather
  is two word-gathers, and the join kernels' positions and compared
  words all fit one);
- ``hlo-loop``           — a ``while`` in a kernel that declares itself
  free of them (``export_check(..., no_loop=True)``: join_probe_counts
  searches by rows of pivots, one row gather a level; a binary search
  step inside a ``while`` cost the chip nine of those).

``python -m opentenbase_tpu.analysis.hlo_audit`` exports the kernel
battery (add ``--full`` for the live query battery with fused/mesh
program capture) and exits nonzero on findings.  The legacy report keys
(``mode``/``f64``/``export_errors``/``kernels``/``programs``/
``battery``/``ok``) are preserved — tests/test_tpu_lowering.py keeps
working against ``utils.lowering_check``, which now delegates here.
"""

from __future__ import annotations

import argparse
import json
import re
import sys

from .core import Finding

# element type in both scalar (tensor<f64>) and shaped (tensor<4xf64>)
# spellings — a plain \b misses the latter ('x' is a word character)
_F64 = re.compile(r"(?:\b|(?<=x))f64\b")
_TRANSFER = re.compile(
    r"stablehlo\.(send|recv|infeed|outfeed)\b"
    r"|custom_call\s*@(xla_python_cpu_callback|xla_ffi_python_cpu_"
    r"callback|HostCompute|xla\.host_transfer)"
    r"|mhlo\.(send|recv)\b")
_DYNSHAPE = re.compile(
    r"stablehlo\.(real_dynamic_slice|dynamic_reshape|dynamic_pad"
    r"|dynamic_broadcast_in_dim|dynamic_gather|dynamic_iota"
    r"|dynamic_conv)\b"
    r"|tensor<(\?|\d+x\?|[0-9x]*\?x)")
_SCATTER_SORT = re.compile(r"stablehlo\.(scatter|sort)\b")
_CONDITIONAL = re.compile(r"stablehlo\.(case|if)\b")
# the op's type signature, `: (table, indices) -> result`, not its
# attributes (`slice_sizes = array<i64: 1>` is no tensor)
_WIDE_GATHER = re.compile(
    r"stablehlo\.gather.*:\s*\([^)]*i64>[^)]*\)\s*->")
_LOOP = re.compile(r"stablehlo\.while\b")


def scan_hlo_text(label: str, txt: str, no_scatter_sort: bool = False,
                  no_conditional: bool = False,
                  no_wide_gather: bool = False,
                  no_loop: bool = False) -> list:
    """Scan one exported program's MLIR text; one finding per rule per
    program, at the first offending line."""
    findings = []
    rules = [
        ("hlo-f64", _F64, "f64 tensor type in exported StableHLO"),
        ("hlo-host-transfer", _TRANSFER,
         "host transfer / callback op in exported StableHLO"),
        ("hlo-dynamic-shape", _DYNSHAPE,
         "dynamic-shape op in exported StableHLO")]
    if no_scatter_sort:
        rules.append(("hlo-scatter-sort", _SCATTER_SORT,
                      "scatter or sort in a kernel declared free of both"))
    if no_conditional:
        rules.append(("hlo-conditional", _CONDITIONAL,
                      "conditional in a kernel declared free of them"))
    if no_wide_gather:
        rules.append(("hlo-wide-gather", _WIDE_GATHER,
                      "64-bit gather in a kernel declared free of them"))
    if no_loop:
        rules.append(("hlo-loop", _LOOP,
                      "while in a kernel declared free of them"))
    for rule, rx, msg in rules:
        m = rx.search(txt)
        if m:
            line = txt.count("\n", 0, m.start()) + 1
            findings.append(Finding(rule, label, line, "",
                                    f"{msg} ({m.group(0).strip()})"))
    return findings


def _sds_of(tree):
    import jax

    def leaf(a):
        a = jax.numpy.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    return jax.tree.map(leaf, tree)


def export_check(fn, args, label: str, report: dict,
                 no_scatter_sort: bool = False,
                 no_conditional: bool = False,
                 no_wide_gather: bool = False, no_loop: bool = False):
    """Export `fn(*args)` for platform 'tpu'; scan the StableHLO and
    record findings (f64 hits also land in the legacy report keys)."""
    import jax
    from jax import export
    try:
        exp = export.export(
            fn if isinstance(fn, jax.stages.Wrapped) else jax.jit(fn),
            platforms=("tpu",))(*_sds_of(args))
        txt = exp.mlir_module()
    except Exception as e:  # noqa: BLE001 — report, don't crash the scan
        report.setdefault("export_errors", []).append(
            f"{label}: {type(e).__name__}: {e}")
        return
    report["programs"] = report.get("programs", 0) + 1
    for f in scan_hlo_text(label, txt, no_scatter_sort, no_conditional,
                           no_wide_gather, no_loop):
        report.setdefault("findings", []).append(f)
        if f.rule == "hlo-f64":
            report.setdefault("f64", []).append(label)


def check_kernels(report: dict):
    """Every ops/kernels.py kernel at two size classes."""
    import jax.numpy as jnp

    from ..ops import kernels as K
    from ..utils.dtypes import device_float
    DF = device_float()
    for n in (1024, 65536):
        f = jnp.zeros(n, DF)
        i = jnp.zeros(n, jnp.int64)
        v = jnp.zeros(n, bool)
        export_check(lambda m: K.live_positions(m, out_size=256), (v,),
                     f"live_positions/{n}", report, no_scatter_sort=True)
        # the dense aggregate is a compare-and-reduce at every domain
        # (Q1's 6 groups, Q5's 25): no scatter and no sort
        for groups in (6, 25, 64):
            export_check(
                lambda g, m, a, groups=groups: K.grouped_agg_dense(
                    g, m, a, num_groups=groups,
                    agg_kinds=("sum", "count", "min", "max", "sumf")),
                (i, v, (i, i, i, f, f)),
                f"grouped_agg_dense/{n}/{groups}", report,
                no_scatter_sort=True)
        export_check(
            lambda k, m, a: K.grouped_agg_sort(
                k, m, a, max_groups=n,
                agg_kinds=("sum", "count", "min", "max", "sumf")),
            ((i, i), v, (i, i, i, f, f)),
            f"grouped_agg_sort/{n}", report)
        # the shape the cells run since a traced aggregate has an output
        # class of its own (executor._agg_class): fewer slots than rows,
        # one key of host-known span (Q17's), SUM and COUNT by running
        # totals — ONE sort, no `lax.cond`, and a slot search in one pass
        # (no `lax.map`).  Its per-ROW reads are 32-bit row gathers
        # through an int32 perm (tests/test_tpu_compile.py holds that at
        # the input's lanes); the sort and the per-SLOT reads of a 64-bit
        # running sum (`(cumsum - vals)[starts]`) are the kernel's own:
        # those two rules cannot be declared of it
        export_check(
            lambda k, m, a: K.grouped_agg_sort(
                k, m, a, max_groups=n // 4, agg_kinds=("sum", "count"),
                key_spans=(n // 2,)),
            ((i,), v, (i, i)), f"grouped_agg_sort/{n}/{n // 4}", report,
            no_conditional=True, no_loop=True)
        # both arms of join_build: nothing known of the key's range
        # (exact sort) and a host-known span (packed sort); both word
        # widths of join_probe_counts' search (the int64's halves; int32
        # offsets, over a narrow span and a wide one): row gathers of
        # 32-bit words, no loop, no scatter
        for span in (None, n // 2):
            export_check(
                lambda k, m, span=span: K.join_build(k, m, key_span=span),
                (i, v), f"join_build/{n}/{span}", report,
                no_conditional=True)
        for span in (None, n // 2, 4 * n):
            export_check(
                lambda s_, k, m, span=span: K.join_probe_counts(
                    s_, k, m, key_span=span),
                (i, i, v), f"join_probe_counts/{n}/{span}", report,
                no_scatter_sort=True, no_conditional=True,
                no_wide_gather=True, no_loop=True)
        export_check(
            lambda lo, c, p: K.join_expand(lo, c, p, out_size=2 * n,
                                           left_outer=True,
                                           probe_valid=None),
            (i, i, i), f"join_expand/{n}", report, no_wide_gather=True)
        # a semi or anti join's `<>` residual answered by a mask: the
        # packed single sort (both spans known) and the two-key sort,
        # then two row gathers of 32-bit words a probe row: no loop, no
        # scatter, no conditional, no 64-bit gather
        for spans in ((None, None), (n // 2, 7)):
            export_check(
                lambda k, m, c, spans=spans: K.join_build_minor(
                    k, m, c, key_span=spans[0], minor_span=spans[1]),
                (i, v, i), f"join_build_minor/{n}/{spans[0]}", report,
                no_conditional=True)
        i32 = jnp.zeros(n, jnp.int32)
        export_check(
            lambda lo, c, sm, pm, ok: K.range_differs(
                lo, c, sm, pm[0], pm, ok),
            (i32, i32, i32, i, v), f"range_differs/{n}", report,
            no_scatter_sort=True, no_conditional=True,
            no_wide_gather=True, no_loop=True)
        # the exchange's pack: a destination's slot finds its source row
        # by rows of 32-bit pivots (four destinations, one level of row
        # gathers at the larger class) and the columns, null masks and
        # floats among them, come through that index as ONE gather of
        # 32-bit rows: no scatter, no sort, no loop, no conditional, no
        # 64-bit gather
        export_check(
            lambda d: K.bucket_rows(d, ndn=4, bucket=n // 4), (i32,),
            f"bucket_rows/{n}", report, no_scatter_sort=True,
            no_conditional=True, no_wide_gather=True, no_loop=True)
        export_check(
            lambda a, b, c, m, at, ok: K.take_rows((a, b, c, m), at, ok),
            (i, i32, f, v, i32, v), f"take_rows/{n}", report,
            no_scatter_sort=True, no_conditional=True,
            no_wide_gather=True, no_loop=True)
        export_check(K.semi_mask, (i,), f"semi_mask/{n}", report)
        export_check(lambda c, pv: K.anti_mask(c, pv), (i, v),
                     f"anti_mask/{n}", report)
        export_check(
            lambda k1, k2, m, p1, p2: K.sort_rows(
                (k1, k2), m, (p1, p2), descs=(False, True), limit=128),
            (i, f, v, i, f), f"sort_rows/{n}", report)
        export_check(
            lambda c1, c2: K.bucket_ids((c1, c2), num_buckets=4096),
            (i, i), f"bucket_ids/{n}", report)
        export_check(
            lambda a, b, c, d: K.visibility_mask(
                a, b, c, d, jnp.int64(5), jnp.int64(7), jnp.int64(-1)),
            (i, i, i, i), f"visibility_mask/{n}", report)
    report["kernels"] = report.get("programs", 0)


def audit(full: bool = True) -> dict:
    """Run the audit; returns the combined legacy+findings report."""
    from ..utils.dtypes import mode

    report: dict = {"mode": mode(), "f64": [], "export_errors": [],
                    "findings": []}
    check_kernels(report)

    if full:
        from ..exec import fused, mesh_exec
        from ..utils.lowering_check import run_battery
        seen: set = set()

        def hook(tag, fn, args):
            key = (tag, id(fn))
            if key in seen:
                return
            seen.add(key)
            export_check(fn, args, f"{tag}/{len(seen)}", report)

        fused.EXPORT_HOOK = hook
        mesh_exec.EXPORT_HOOK = hook
        try:
            results = run_battery()
        finally:
            fused.EXPORT_HOOK = None
            mesh_exec.EXPORT_HOOK = None
        report["battery"] = {k: (v if isinstance(v, str) else len(v))
                             for k, v in results.items()}

    # f64 is the documented CONTRACT of x64 mode (bit-matching the CPU
    # oracles) — the hlo-f64 rule only bites under the tpu dtype mode.
    if report["mode"] == "x64":
        for f in report["findings"]:
            if f.rule == "hlo-f64":
                f.suppressed = True
    unsup = [f for f in report["findings"] if not f.suppressed]
    report["unsuppressed"] = len(unsup)
    report["ok"] = (not unsup and not report["export_errors"]
                    and (report["mode"] == "x64" or not report["f64"]))
    report["findings"] = [f.as_dict() for f in report["findings"]]
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="opentenbase_tpu.analysis.hlo_audit",
        description="StableHLO audit of exported engine programs")
    ap.add_argument("--full", action="store_true",
                    help="also run the live query battery and audit "
                         "captured fused/mesh programs")
    ap.add_argument("--kernels-only", action="store_true",
                    help="audit only the kernel battery (fast path "
                         "used by the CI gate)")
    args = ap.parse_args(argv)
    report = audit(full=args.full and not args.kernels_only)
    print(json.dumps(report, default=str))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
