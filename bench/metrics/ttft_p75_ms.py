"""75th percentile, over every request due in the window, of the time from
its due time to its first token on the host (host clock). A window holds
about 40 requests, so ten of them lie beyond this percentile."""
from bench.stats import percentile


def read(rec):
    v = [(q["times"][0] - q["due"]) * 1e3 for q in rec.requests
         if q["in_window"] and q["times"]]
    return percentile(v, 75) if v else None
