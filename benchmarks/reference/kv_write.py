"""An acknowledged single-row INSERT, then its read-back: the row written is
the row read.  `precision="stale"` is the control that breaks the guarantee:
a read served from before the write finds nothing."""


def expected(data, params, shared, precision="exact"):
    if precision == "stale":
        return []
    return [(params["k"], params["v"], float(params["amt"]), params["note"])]
