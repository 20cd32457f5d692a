"""Least time of the decode steps the trace holds over their summed device
time, in percent. Least time is the larger of required bytes over HBM
bandwidth and required FLOPs over peak; required bytes are the weights once
and the live KV positions of the active slots (the family's cost model)."""
from bench.metrics import _share


def read(rec):
    return _share.roofline(rec, "decode", rec.traced_decode)
