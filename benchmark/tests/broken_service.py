"""`verify-service` with the timed path broken underneath: the first
verdict of every device round is altered where it is produced
(`BatchVerifier._dispatch`, the call that returns a verify program's
bitmap). test_end_to_end.py runs a cell against it and has to see
`correct` come out false."""

import sys

sys.path.insert(0, sys.argv.pop(1))  # the checkout's root

from tendermint_tpu.__main__ import main  # noqa: E402
from tendermint_tpu.crypto.batch_verifier import BatchVerifier  # noqa: E402

_dispatch = BatchVerifier._dispatch


def altered(self, *args, **kw):
    out = _dispatch(self, *args, **kw).copy()
    out[0] = not out[0]
    return out


BatchVerifier._dispatch = altered
sys.exit(main(["verify-service"] + sys.argv[1:]))
