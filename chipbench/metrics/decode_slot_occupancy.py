"""Tokens the decode rounds delivered / (rounds x slots x decode_block), in
percent: how full the scheduler keeps the decode batch."""


def read(run):
    rounds = run.recorder.rounds
    cap = sum(r["steps"] * r["slots"] for r in rounds)
    return 100.0 * sum(r["delivered"] for r in rounds) / cap if cap else None
