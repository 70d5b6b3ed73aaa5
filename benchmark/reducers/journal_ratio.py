"""Sum of one field of one journal event over the sum of another, over the
events of the measured window, in %: `num` and `den` are [event, field]
with field "a" or "b". The journal's window is exact (run.py cuts it to the
measured seconds); the Engine.metrics() counters that hold the same account
are scraped when the traced run's capture has been read, some seconds after
the window's end, when the generator has already cut its streams. None
where the program does not journal the events."""


def total(events, event, field):
    vals = [e[field] for e in events if e["event"] == event]
    return sum(vals) if vals else None


def read(ctx, num, den):
    n, d = total(ctx["journal"], *num), total(ctx["journal"], *den)
    if n is None or not d:
        return None
    return 100.0 * n / d
