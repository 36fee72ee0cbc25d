"""Tokens the requests kept from the decode rounds over the rows the rounds
dispatched (slots x steps), in percent: the program's counters
``decode.tokens`` / ``decode.rows``, counted by the scheduler over the
run."""
import progtrace


def read(run):
    c = progtrace.counters(run)
    if not c or not c.get("decode.rows"):
        return None
    return 100.0 * c.get("decode.tokens", 0) / c["decode.rows"]
