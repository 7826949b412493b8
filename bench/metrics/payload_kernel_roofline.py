"""Roofline share of the payload kernels (``payload_store`` +
``payload_fetch``) over a whole profiled call: the least time their work
needs at the chip's HBM bandwidth, over the device time they took, in %.
The work is what the operations need (``work.payload_bytes``), not what
today's kernels move.  Not read from a trace that lost kernel launches,
nor from one in which a device's ops go unnamed."""
from bench import tracefile
from bench.work import payload_bytes


def read(run):
    call = run.traced
    if run.trace is None or call is None or not call.traced_whole \
            or not run.trace_complete \
            or not tracefile.named_on_every_device(run.trace):
        return None
    lo, hi = tracefile.window(run.trace)
    measured = tracefile.kernel_ns(run.trace, lo, hi,
                                   tracefile.NAMED_KERNELS) / 1e9
    if measured <= 0:
        return None
    need = payload_bytes(run.cell.config["park"]["row_bytes"],
                         call.store_rows, call.stored_rows,
                         call.fetch_rows, call.fetched_rows)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / measured
