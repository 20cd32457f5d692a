"""Output tokens delivered to the host during the window, per second of the
window."""


def read(rec):
    n = sum(1 for q in rec.requests for t in q["times"]
            if 0.0 <= t < rec.seconds)
    return n / rec.seconds if n else None
