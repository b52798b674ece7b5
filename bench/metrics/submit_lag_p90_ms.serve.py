"""90th percentile (nearest rank) of submit time minus due time over the
window's requests: how late the load generator ran (host clock)."""

from bench.metrics_util import percentile


def read(run):
    lag = run.got.get("submit_lag")
    if not lag:
        return None
    return 1e3 * percentile(lag, 90)
