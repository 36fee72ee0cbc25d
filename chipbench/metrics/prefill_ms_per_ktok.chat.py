"""Host time in prefill calls per 1000 prompt tokens prefilled (the chat
cell's reading; the same as the long-context cell's)."""


def read(run):
    calls = run.recorder.prefills
    tokens = sum(c["tokens"] for c in calls)
    return 1e6 * sum(c["t1"] - c["t0"] for c in calls) / tokens if tokens else None
