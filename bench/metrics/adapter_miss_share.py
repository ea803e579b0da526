"""Share of the window's admission lookups whose adapter was not resident in
HBM: 1 - lora_hbm_hits / lookups, deltas of ManagerStats."""

from perfkit import readers


def read(run):
    n = run.stat_delta("lookups")
    hits = readers.share(run.stat_delta("lora_hbm_hits"), n)
    return None if hits is None else readers.pct(1.0 - hits)
