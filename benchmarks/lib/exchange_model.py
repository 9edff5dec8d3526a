"""What the exchange layer must move, and the least time the interconnect
could take for it.  The bytes are the program's own count: exec/mesh_exec
fixes, when it traces a mesh program, how many all_to_all exchanges the
program holds and the bytes ONE chip sends over ICI in them (ndn - 1 of the
ndn equal buckets of every column, null mask and the validity flags), and
puts both on the statement's `execute` span (`exchanges`, `exchange_bytes`
of `last_query_stats()`).  A program that counts no such thing (the parent
of the PR that brought the counters) gives nothing to reckon with."""

from . import stats


def sent_bytes_by_class(step_stats, counter="exchange_bytes"):
    """{statement class: bytes one chip sends in one statement}, from the
    (class, server-side stats) pairs of a run: the counter is fixed per
    program, so the median over a class's replies is its every reply's."""
    by = {}
    for cls, st in step_stats:
        if st and counter in st:
            by.setdefault(cls, []).append(st[counter])
    return {cls: stats.median(xs) for cls, xs in by.items()}


def least_seconds(sent_bytes, peaks, peak="ici_bits_per_s"):
    """The least time one chip's interconnect could take to send
    `sent_bytes`: the PUBLISHED per-chip figure of lib/peaks.json, all of a
    chip's links together, which a 2x2 host's three peers cannot all use."""
    return sent_bytes / (peaks[peak] / 8.0)
