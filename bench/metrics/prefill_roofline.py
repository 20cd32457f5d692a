"""Least time of the prefill forwards the trace holds (chunked or one-shot)
over their summed device time, in percent, by the same rule as
``decode_roofline`` over the prompt tokens each forward processed."""
from bench.metrics import _share


def read(rec):
    return _share.roofline(rec, "prefill", rec.traced_prefill)
