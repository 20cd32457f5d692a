"""A run of the harness on the CPU at smoke widths: the generator, the
engine, the delivery thread and the comparison, with the chip check skipped.
The fault test breaks the timed path underneath and sees ``correct`` come out
false."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import smoke

SECONDS = 2.5


def _run(config, seed, fault=None):
    c = smoke.cell(config)
    s = harness.Session(c, seed, SECONDS, device_kind="TPU v5 lite")
    if fault is not None:
        fault(s.engine)
    rec = harness.serve(s)
    wseed = s.wseed
    s.free()
    sample = harness.sample_finished(rec, seed)
    gaps = harness.reference_gaps(c, wseed, sample)
    return c, s, rec, sample, harness.checks(c, rec, harness.readings(gaps))


def test_every_due_request_is_accounted_for():
    c, s, rec, sample, ch = _run(smoke.DENSE, 2**33 + 17)
    due = [a for a in s.arrivals if a.in_window]
    window = [q for q in rec.requests if q["in_window"]]
    assert len(window) == len(due) > 10
    assert all(q["times"] for q in window)            # every first token came
    assert rec.notes["compiles_in_window"] == 0
    for q in rec.requests:                            # stamps in order
        assert q["times"] == sorted(q["times"])
        assert len(q["tokens"]) == len(q["times"]) <= q["max_new_tokens"]
    # TTFT and gaps come from the delivery stamps
    ttft = [(q["times"][0] - q["due"]) * 1e3 for q in window]
    m, missing = harness.read_metrics(rec, c.end_to_end,
                                      {n: "" for n in c.end_to_end})
    assert not missing
    assert m["ttft_p75_ms"]["value"] == pytest.approx(np.percentile(ttft, 75))
    assert all(t > 0 for t in ttft)
    assert len(sample) >= harness.SAMPLE_MIN
    assert harness.passed(ch), ch
    assert ch["served_token_gap"]["value"] == 0.0     # float32 end to end


def _altered_token(eng, step, params, pool, *rest):
    """Slot 0's output token is changed as the compiled step returns it."""
    out = step(params, pool, *rest)
    nxt = out[0].at[0].set((out[0][0] + 1) % eng.cfg.vocab)
    return (nxt,) + tuple(out[1:])


def _state_unchanged(eng, step, params, pool, *rest):
    """The step hands back the KV pool it was given, so the tokens it
    decodes are never stored."""
    kept = jax.tree.map(jnp.copy, pool)          # the step donates its pool
    out = step(params, pool, *rest)
    return out[:2] + (kept,) + tuple(out[3:])


@pytest.mark.parametrize("broken", [_altered_token, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_decode_step_fails_the_comparison(broken):
    """Fault: from the tenth decode step on, the timed path's compiled
    decode step is broken as ``broken`` says."""
    def fault(eng):
        step, calls = eng._decode, [0]

        def wrapped(*args):
            calls[0] += 1
            return step(*args) if calls[0] <= 10 else broken(eng, step, *args)

        eng._decode = wrapped

    c, s, rec, sample, ch = _run(smoke.DENSE, 99, fault=fault)
    assert ch["served_token_gap"]["value"] > c.limits["served_token_gap"]
    assert not harness.passed(ch)
