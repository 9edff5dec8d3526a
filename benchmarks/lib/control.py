"""The control of `correct`: the plain reference put in the program's
place, computed in the nearest precision below the one the configuration
states (float32 where it states exact decimals and one float64 division),
or with one stated guarantee broken (a read served from before an
acknowledged write).  The comparison has to refuse it."""

import numpy as np

from . import compare, params as params_mod


def gaps(mix, precision, statements=None):
    """For each pool member (and, for "each" statements, 32 draws from
    the seed) the control's rows against the reference's:
    [(statement, mismatch | None, avg_gap, ulp_gap)]."""
    out = []
    for st in mix.statements:
        if statements and st.name not in statements:
            continue
        if st.draw == "pool":
            cases = [p for p, _ in mix.pools[st.name]]
        else:
            rng = np.random.default_rng([mix.seed, 0x6374726c])
            cases = [params_mod.draw(st.domains, rng, mix.key_laws, 0, n)
                     for n in range(32)]
        for p in cases:
            want = st.reference.expected(mix.data, p, mix.shared)
            got = st.reference.expected(mix.data, p, mix.shared, precision)
            out.append((st.name, *compare.rows_gap(got, want,
                                                   st.float_cols)))
    return out
