"""Share of presented history tokens the cache manager served from its pool
(HBM or host tier) over the window: deltas of ManagerStats."""

from perfkit import readers


def read(run):
    hit = run.stat_delta("kv_hbm_hit_tokens") + run.stat_delta("kv_host_hit_tokens")
    return readers.pct(readers.share(hit, run.stat_delta("history_tokens")))
