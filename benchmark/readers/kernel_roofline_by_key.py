"""The share of their roofline of the programs that verify ONE key
type, in per cent: `kernel_roofline`'s reading with the work of that
key type (`work_by_key.py`: ed25519's count is `work.py`'s, secp256k1's
its own). The metric file names `programs` and `key_type`; the rows are
the load generator's own tally of that key type in the traced span
(`traced_rows_by_key_type`), never the program's shapes. Left out where
the trace holds no such program or the span no such row.
"""

import work_by_key
from readers.kernel_time import matching


def read(ctx: dict, spec: dict):
    count, seconds = matching(ctx, spec)
    rows = ctx["traced_rows_by_key_type"].get(spec["key_type"], 0)
    if not count or not rows:
        return None
    least, _ = work_by_key.least_seconds(
        rows, ctx["device"]["kind"], spec["key_type"]
    )
    return 100.0 * least / seconds
