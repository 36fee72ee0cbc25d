"""Host time in prefill calls per 1000 prompt tokens prefilled (the long-context
cell's reading)."""


def read(run):
    calls = run.recorder.prefills
    tokens = sum(c["tokens"] for c in calls)
    return 1e6 * sum(c["t1"] - c["t0"] for c in calls) / tokens if tokens else None
