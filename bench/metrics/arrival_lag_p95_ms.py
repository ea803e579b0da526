"""95th percentile of how late the load generator submitted each request
due in the window (submit minus due, harness clock)."""

from perfkit import readers, window


def read(run):
    return readers.ms(readers.p95(window.arrival_lags(run.obs.values(), run.w0, run.w1)))
