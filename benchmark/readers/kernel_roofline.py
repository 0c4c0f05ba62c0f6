"""The verify kernels' share of their roofline, in per cent: the least
time the chip could take for the signatures REQUESTED in the traced
span (work.py's one count for every tier and bucket, over peaks.json)
divided by the device time of the verify programs in that span. The
count comes from the load generator's own tally of rows, never from
the program's shapes. A metric file that gives `key_type` counts the
rows of that key type alone (`traced_rows_by_key_type`), so that in a
cell of mixed key types the work of one type is divided by the time of
that type's programs; without the key every traced row counts.
"""

import work
from readers.kernel_time import matching


def read(ctx: dict, spec: dict):
    count, seconds = matching(ctx, spec)
    rows = (
        ctx["traced_rows_by_key_type"].get(spec["key_type"], 0)
        if "key_type" in spec else ctx["traced_rows"]
    )
    if not count or not rows:
        return None
    least, _ = work.least_seconds(rows, ctx["device"]["kind"])
    return 100.0 * least / seconds
