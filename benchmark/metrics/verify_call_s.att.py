"""verify_call_s.att: mean host-clock seconds of one device batch
verification (one chunk of signature sets) that started in the window."""


def read(run):
    calls = [c for c in run.calls if run.t0 <= c.start < run.t_end]
    if not calls:
        return None
    return sum(c.end - c.start for c in calls) / len(calls)
