#!/usr/bin/env python3
"""Chip smoke run: full-width TinyLlama-1.1B through the serving engine on one TPU.

    python chip_smoke.py [--seed N]        # one chip: phases A and B
    python chip_smoke.py --chips 4         # four chips: the sharded train phase only

Phase A serves ~16 greedy requests (64-512 prompt tokens, 32 new tokens each)
through ``Engine`` with the dense KV layout and checks the streams against
``serve_sequential`` (one request at a time, batch width 1). Where bf16 breaks
bitwise equality between batch widths, the script says so and holds each
diverging stream to a float32 teacher-forced forward pass instead: at every
generated position the engine's token must be within ``LOGIT_TOL`` of the
reference's best logit. The same requests are then served again on a fresh
engine, and the two streams must be identical (deterministic dispatch).

Phase B serves the same requests on the paged KV layout twice: the XLA gather
decode and the compiled Pallas paged-attention kernel (``interpret=False``).
The streams must agree under the same rule.

``--chips 4`` runs only phase C: a few train steps of full-width TinyLlama on a
2x2 (data x model) mesh through ``trainer.jit_train_step`` (GSPMD) and the first
step through ``fsdp.make_fsdp_train_step``; the first-step losses must agree,
losses must be finite and fall, and the state must be spread over all four
devices.

The script fails (non-zero exit, no result line) when JAX finds no TPU; there
is no CPU fallback. Records (compile seconds, warm tokens/s, peak bytes) are
printed with the device they ran on. The last line of standard output is the
JSON result ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ShapeCfg, config  # noqa: E402
from repro.core.devices import peaks  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import api, transformer  # noqa: E402
from repro.runtime.engine import (Engine, EngineConfig, RequestSpec,  # noqa: E402
                                  serve_sequential)

ARCH = "tinyllama-1.1b"
SLOTS = 8
BUCKETS = (128, 512)
MAX_SEQ = 2048
NEW_TOKENS = 32
# float32 teacher-forced reference: the chosen token at each generated
# position must score within this many logits of the reference's best token.
# bf16 rounds a logit of magnitude ~4 to a 2**-6 grid, and batch width changes
# the bf16 accumulation order through 22 layers. On a TPU v5e the dense
# engine, serve_sequential and both paged decodes stayed within 0.041.
LOGIT_TOL = 0.1


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


def make_specs(cfg, n: int, seed: int, lo: int = 64, hi: int = 512,
               new_tokens: int = NEW_TOKENS):
    rng = np.random.default_rng(seed)
    return [RequestSpec(prompt=tuple(int(t) for t in rng.integers(
                0, cfg.vocab, size=int(rng.integers(lo, hi + 1)))),
                        max_new_tokens=new_tokens)
            for _ in range(n)]


def serve(cfg, params, specs, ecfg: EngineConfig):
    """Serve ``specs`` on a fresh engine; returns (streams, seconds, stats)."""
    engine = Engine(cfg, ecfg, params=params)
    t0 = time.perf_counter()
    reqs = engine.run(specs)
    streams = [engine.finalize_request(r) for r in reqs]
    dt = time.perf_counter() - t0
    check(all(r.state == "done" for r in reqs),
          f"requests not done: {[r.state for r in reqs]}")
    check(all(len(s) == sp.max_new_tokens for s, sp in zip(streams, specs)),
          "stream lengths differ from max_new_tokens")
    return streams, dt, engine.stats()


def padded(prompt, buckets):
    b = next(b for b in sorted(buckets) if b >= len(prompt))
    toks = np.zeros((b,), np.int32)
    toks[:len(prompt)] = prompt
    return toks


def reference_gaps(cfg, params, specs, streams, buckets):
    """Per stream, the largest gap between the float32 teacher-forced
    reference's best logit and the logit of the token the stream chose, over
    every generated position. The engine pads each prompt to its bucket and
    samples the first token at the bucket's last position; the reference
    replays exactly that sequence."""
    cfg32 = dataclasses.replace(cfg, compute_dtype="float32")
    length = max(buckets) + max(sp.max_new_tokens for sp in specs)

    @jax.jit
    def gaps(params, toks, start, chosen):
        hidden, _ = transformer.forward(cfg32, params, toks[None])
        logits = transformer.logits_fn(cfg32, params, hidden)[0]
        rows = jax.lax.dynamic_slice_in_dim(logits, start, chosen.shape[0])
        best = rows.max(axis=-1)
        mine = jnp.take_along_axis(rows, chosen[:, None], axis=-1)[:, 0]
        return best - mine

    out = []
    for sp, stream in zip(specs, streams):
        seq = np.concatenate([padded(sp.prompt, buckets),
                              np.asarray(stream[:-1], np.int32)])
        toks = np.zeros((length,), np.int32)
        toks[:len(seq)] = seq
        start = len(seq) - len(stream)
        g = gaps(params, jnp.asarray(toks), jnp.int32(start),
                 jnp.asarray(stream, jnp.int32))
        out.append(float(jnp.max(g)))
    return out


def agree(name, got, want, cfg, params, specs, buckets):
    """Bitwise stream equality, or — where it breaks — every diverging
    stream within LOGIT_TOL of the float32 reference at every position."""
    diff = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
    if not diff:
        say(f"{name}: {len(got)}/{len(got)} streams bitwise identical")
        return
    say(f"{name}: bitwise equality broken for {len(diff)}/{len(got)} streams "
        f"(first at request {diff[0]}); comparing next-token logits against "
        f"the float32 reference within {LOGIT_TOL} instead")
    sub = [specs[i] for i in diff]
    g_got = reference_gaps(cfg, params, sub, [got[i] for i in diff], buckets)
    g_want = reference_gaps(cfg, params, sub, [want[i] for i in diff],
                            buckets)
    say(f"{name}: max logit gap to the float32 reference: "
        f"{max(g_got)!r} (first), {max(g_want)!r} (second)")
    check(max(g_got) <= LOGIT_TOL and max(g_want) <= LOGIT_TOL,
          f"{name}: a stream leaves the float32 reference by more than "
          f"{LOGIT_TOL} logits")


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def phase_a(cfg, params, specs, device, *, slots=SLOTS, buckets=BUCKETS,
            max_seq=MAX_SEQ):
    """Dense engine vs serve_sequential, then a run-twice determinism check.
    Returns the dense streams."""
    ecfg = EngineConfig(slots=slots, prompt_buckets=buckets, max_seq=max_seq)
    streams, cold_s, _ = serve(cfg, params, specs, ecfg)
    again, warm_s, st = serve(cfg, params, specs, ecfg)
    say(f"phase A [{device.device_kind}]: cold run {cold_s!r} s (compile "
        f"included), warm run {warm_s!r} s, warm decode "
        f"{st['tokens_per_s']!r} tok/s, peak_bytes_in_use "
        f"{peak_bytes(device)}")
    check(again == streams, "phase A: two fresh engines served different "
                            "streams for the same requests")
    say("phase A: run-twice determinism: streams identical")
    seq = serve_sequential(cfg, params, specs, max_seq=max_seq,
                           prompt_buckets=buckets)
    want = [seq["tokens"][i + 1] for i in range(len(specs))]
    agree("phase A engine vs serve_sequential", streams, want, cfg, params,
          specs, buckets)
    gaps = reference_gaps(cfg, params, specs, streams, buckets)
    say(f"phase A: engine streams vs float32 reference: max logit gap "
        f"{max(gaps)!r}")
    say("phase A: PASS")
    return streams


def phase_b(cfg, params, specs, device, dense_streams, *, slots=SLOTS,
            buckets=BUCKETS, max_seq=MAX_SEQ, interpret=False):
    """Paged layout: XLA gather decode vs the compiled Pallas kernel."""
    runs = {}
    for kernel in ("xla", "pallas"):
        ecfg = EngineConfig(slots=slots, prompt_buckets=buckets,
                            max_seq=max_seq, kv_layout="paged",
                            decode_kernel=kernel, interpret=interpret)
        streams, cold_s, _ = serve(cfg, params, specs, ecfg)
        runs[kernel] = streams
        say(f"phase B [{device.device_kind}]: paged/{kernel} "
            f"(interpret={interpret}) run {cold_s!r} s (compile included), "
            f"peak_bytes_in_use {peak_bytes(device)}")
    agree("phase B paged/pallas vs paged/xla", runs["pallas"], runs["xla"],
          cfg, params, specs, buckets)
    agree("phase B paged/xla vs dense", runs["xla"], dense_streams, cfg,
          params, specs, buckets)
    say("phase B: PASS")


def phase_c(cfg, mesh, *, seq_len=512, batch=8, steps=4, seed=0):
    """Sharded training on a (data x model) mesh: GSPMD steps vs the explicit
    FSDP first step."""
    from repro.core import plans
    from repro.data import DataConfig, ShardedLMDataset
    from repro.runtime import trainer
    from repro.runtime.fsdp import make_fsdp_train_step

    plan = plans.make_plan(cfg, ShapeCfg("chip_smoke", "train", seq_len,
                                         batch), microbatches=1)
    lr = dict(peak_lr=1e-3, warmup_steps=1, total_steps=100)
    ds = ShardedLMDataset(DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                                     global_batch=batch))
    with mesh:
        gstep, _, (state_sh, batch_sh) = trainer.jit_train_step(
            cfg, plan, mesh, **lr)
        fstep, _, (fstate_sh, fbatch_sh) = make_fsdp_train_step(
            cfg, plan, mesh, **lr)
        init = jax.jit(functools.partial(trainer.init_state, cfg),
                       out_shardings=state_sh)
        host_batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

        state = jax.device_put(init(jax.random.key(seed)), fstate_sh)
        t0 = time.perf_counter()
        fstate, fm = fstep(state, jax.device_put(host_batch, fbatch_sh))
        f_loss = float(fm["loss"])
        f_s = time.perf_counter() - t0
        del state, fstate          # the input state was donated

        state = init(jax.random.key(seed))
        devices = set(mesh.devices.flat)
        per_dev = {d: 0 for d in devices}
        total = 0
        for leaf in jax.tree.leaves(state):
            total += leaf.nbytes
            for sh in leaf.addressable_shards:
                per_dev[sh.device] += sh.data.nbytes
        say(f"phase C: state {total} bytes; per device "
            f"{sorted(per_dev.values())}")
        check(all(v > 0 for v in per_dev.values()),
              "phase C: a device holds no part of the train state")
        check(max(per_dev.values()) < total / 2,
              "phase C: the train state is not sharded (a device holds more "
              "than half of it)")
        b = jax.device_put(host_batch, batch_sh)
        losses, times = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            state, m = gstep(state, b)
            losses.append(float(m["loss"]))
            times.append(time.perf_counter() - t0)
    say(f"phase C: gspmd losses {losses!r}; step seconds {times!r} "
        f"(first includes compile)")
    say(f"phase C: fsdp first-step loss {f_loss!r} ({f_s!r} s, compile "
        f"included)")
    check(all(np.isfinite(losses)) and np.isfinite(f_loss),
          "phase C: non-finite loss")
    check(losses[-1] < losses[0], f"phase C: loss did not fall: {losses}")
    # same params, same batch: the two lowerings reduce in different orders
    check(abs(f_loss - losses[0]) <= 1e-3 * abs(losses[0]),
          f"phase C: first-step losses disagree: fsdp {f_loss} vs gspmd "
          f"{losses[0]} (tolerance 1e-3 relative)")
    say("phase C: PASS")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded train phase on a 2x2 mesh")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this script runs on the chip "
              "only", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    say(f"compile cache: {enable_compile_cache()}")
    pk = peaks(dev.device_kind)
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    say(f"peaks [{dev.device_kind}]: {pk}; bytes_limit {limit}")
    check(limit is not None and limit <= pk.hbm_bytes,
          f"device memory {limit} exceeds the table's {pk.hbm_bytes}")

    cfg = config(ARCH)
    if args.chips == 4:
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        phase_c(cfg, mesh, seed=args.seed)
    else:
        t0 = time.perf_counter()
        params = jax.jit(functools.partial(api.init_params, cfg))(
            jax.random.key(args.seed))
        jax.block_until_ready(params)
        say(f"params: {cfg.name} {sum(x.size for x in jax.tree.leaves(params))}"
            f" parameters ({time.perf_counter() - t0!r} s)")
        specs = make_specs(cfg, args.requests, args.seed)
        dense = phase_a(cfg, params, specs, dev)
        phase_b(cfg, params, specs, dev, dense)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
