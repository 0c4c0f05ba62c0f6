"""The verify kernels' share of their roofline, in per cent: the least
time the chip could take for the signatures REQUESTED in the traced
span (work.py's one count for every tier and bucket, over peaks.json)
divided by the device time of the verify programs in that span. The
count comes from the load generator's own tally of rows, never from
the program's shapes.
"""

import work
from readers.kernel_time import matching


def read(ctx: dict, spec: dict):
    count, seconds = matching(ctx, spec)
    rows = ctx["traced_rows"]
    if not count or not rows:
        return None
    least, _ = work.least_seconds(rows, ctx["device"]["kind"])
    return 100.0 * least / seconds
