"""95th percentile of the gaps between output tokens, over every token
delivered in the window but a request's first; a round that delivers k
tokens of a request counts k gaps of the time since that request's previous
token / k (host clock)."""
import numpy as np


def read(run):
    gaps = run.window_gaps()
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
