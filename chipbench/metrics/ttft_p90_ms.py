"""90th percentile, over every request of the run, of the time from its due
time to its first token (host clock), by linear interpolation between
ranks.  A request that failed, or got no first token, is a miss and ranks
above every other; where misses reach the percentile it has no value (such
a run is not correct)."""
import math


def read(run):
    t = sorted(run.ttft_s().values())
    if not t:
        return None
    h = 0.9 * (len(t) - 1)
    lo, hi = math.floor(h), math.ceil(h)
    if math.isinf(t[hi]):
        return None
    return 1e3 * (t[lo] + (h - lo) * (t[hi] - t[lo]))
