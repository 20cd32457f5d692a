"""Required FLOPs of all prefill and decode work in the traced sub-window
over peak bf16 FLOP/s times the wall time in which the engine had work
(time in ``bench.wait_arrival`` left out), in percent."""
from bench.metrics import _share


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    flops = 0.0
    for role, costs in (("decode", rec.traced_decode),
                        ("prefill", rec.traced_prefill)):
        n = _share.matched(tr, role, costs)
        flops += sum(f for f, _ in costs[:n])
    wait = sum(min(e, tr.window_s) - max(s, 0.0) for name, s, e in tr.host
               if name == "bench.wait_arrival" and e > 0 and s < tr.window_s)
    busy = tr.window_s - wait
    if flops <= 0 or busy <= 0:
        return None
    return 100.0 * flops / (rec.peaks.flops_bf16 * busy * tr.chips)
