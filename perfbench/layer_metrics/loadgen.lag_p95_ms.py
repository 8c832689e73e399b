"""How late the generator sent the requests due in the window (95th
percentile, ms): a starved generator must not read as a fast server.
Source: the generator's own clock. Layer: load generator."""

from lib import stats


def read(run):
    lag = stats.lags(run["records"], run["seconds"])
    return 1e3 * stats.percentile(lag, 95) if lag else None
