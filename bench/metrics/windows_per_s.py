"""Heat windows answered per second. A request's windows count when its
response returns. One pump stamps all of its responses with one time, so
the rate opens at the first pump's responses and counts only those that
return after them, up to the last one before the window closes: the work
of the first pump, done before the rate opens, is not counted, and the
pipeline's fill and flush granularity do not alias the rate."""


def read(run):
    rec = run.record
    done = [(a.done, len(a.ts)) for a in rec.answers
            if a.ok and a.done is not None and a.done <= rec.t_close]
    if not done:
        return None
    t_first = min(t for t, _ in done)
    t_last = max(t for t, _ in done)
    if t_last <= t_first:
        return None
    return sum(n for t, n in done if t > t_first) / (t_last - t_first)
