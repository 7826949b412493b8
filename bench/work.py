"""The work a kernel's semantics need, counted from shapes and counts.

A roofline share compares the least time the chip could take for the
work an operation must do with the time it took.  The work is what the
operation needs, whatever implements it: a kernel that moves more (today's
payload kernels copy the whole resident table through fast memory on
every call) is read against the same work, so its share stays under 100%
and a leaner kernel shows as a higher share.
"""
from __future__ import annotations

INDEX_BYTES = 4     # one int32 table index per row handed to the kernel
MASK_BYTES = 4      # one int32 enable per row


def payload_bytes(row_bytes: int, store_rows: int, stored_rows: int,
                  fetch_rows: int, fetched_rows: int) -> int:
    """HBM bytes that ``payload_store`` + ``payload_fetch`` need.

    ``store_rows``/``fetch_rows`` rows are handed to the kernels, each with
    an index and an enable; ``stored_rows`` of them are read from the
    packets and written to their slots, ``fetched_rows`` slots are read,
    written out to their packets and cleared.
    """
    index = (store_rows + fetch_rows) * (INDEX_BYTES + MASK_BYTES)
    return index + row_bytes * (2 * stored_rows + 3 * fetched_rows)
