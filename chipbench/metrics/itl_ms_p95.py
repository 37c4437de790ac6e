"""itl_ms_p95: 95th percentile of every gap between consecutive output
tokens of one request, both inside the window (host clock)."""

from chipbench.stats import percentile


def read(run):
    return 1e3 * percentile(run.driver.gaps_in_window(), 95)
