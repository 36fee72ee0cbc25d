"""Host time per decode round (ModelRunner.decode_round returns host tokens,
so the device has finished)."""


def read(run):
    rounds = run.recorder.rounds
    if not rounds:
        return None
    return 1e3 * sum(r["t1"] - r["t0"] for r in rounds) / len(rounds)
