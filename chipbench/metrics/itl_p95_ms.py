"""95th percentile of every gap between consecutive output tokens of a
request, both inside the window (tokens returned by one step share its
end time)."""
from cbench import derive
from cbench.stats import percentile


def read(ctx):
    v = derive.token_gaps_s(ctx)
    return 1e3 * percentile(v, 95) if v else None
