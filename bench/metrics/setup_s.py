"""Process start to the lead-in: weights, engine and warm-up (host clock)."""


def read(rec):
    return rec.setup_s
