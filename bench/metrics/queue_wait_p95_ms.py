"""95th percentile of admission minus due time of the requests due in the
window (the engine's admit_time; not yet admitted at the close counts to
the close)."""

from perfkit import readers, window


def read(run):
    return readers.ms(readers.p95(window.queue_waits(run.obs.values(), run.w0, run.w1)))
