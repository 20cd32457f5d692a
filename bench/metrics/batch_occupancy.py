"""Mean share of the decode batch's slots in use over the window
(``EngineStats.batch_occupancy``), in percent."""


def read(rec):
    occ = rec.stats.get("batch_occupancy", 0.0)
    return 100.0 * occ if rec.stats.get("decode_steps") else None
