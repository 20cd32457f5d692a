"""Serving launcher: continuous-batching engine over the UPIR decode plan.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 8 --slots 4 --prompt-len 16 --tokens 16

serves the published widths on the default device (a TPU); add ``--smoke``
for the reduced config that a CPU-only run can afford (with
``JAX_PLATFORMS=cpu``). The persistent compile cache is placed by
``launch.compile_cache`` (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/``).

Requests enter the engine's admission queue; prefill fills free decode slots
and a fixed-width decode batch advances every active sequence one token per
step, recycling slots as sequences finish (see ``runtime.engine``). Dispatch
is capability-driven through the ModelFamily protocol (``models.api``), so
encoder-decoder configs (whisper) serve through the same loop — the launcher
synthesizes stub encoder frames per request. ``--temperature`` / ``--top-k``
/ ``--top-p`` / ``--seed`` turn on device-side sampling; ``--eos-id``
finishes requests on an EOS token via the engine's device-side finished
mask. ``--draft-config`` + ``--lookahead`` switch the engine into the
speculative draft/verify mode (``runtime.speculative``): pass an arch id for
the draft family, or ``self`` for self-speculation with the target's own
weights — greedy streams stay bitwise identical either way.

All lowering + jit artifacts come from the process-wide PlanCache, so repeated
launches in one process never re-run the pass pipeline.

``--policy`` selects the admission scheduling policy (``fifo`` | ``priority``
| ``fair`` | ``sjf``, see ``runtime.scheduling``); ``--priority`` cycles
integer priority classes over the requests, ``--tenant`` cycles tenant names
(``name`` or ``name:weight`` entries — weights feed the ``fair`` policy), and
``--deadline-ms`` attaches a TTFT SLO so the engine reports per-class
attainment. ``--prefix-affinity`` (with ``--prefix-cache``) admits requests
whose prompt pages are already cached first.

Fault tolerance (``runtime.faults``): ``--enforce-deadlines`` sheds queued
requests whose ``--deadline-ms`` SLO already expired (typed ``SHED_DEADLINE``
outcome instead of a late answer); ``--max-queue`` bounds the admission queue
(overflow is a typed ``REJECTED_QUEUE_FULL`` rejection, never an unbounded
pile-up); ``--watchdog-ms`` arms the per-iteration wall-clock watchdog;
``--nan-guard`` arms the device-side finite guard on decode logits;
``--debug-checks`` validates allocator/page-table invariants every tick; and
``--fault-seed``/``--fault-count`` inject a seed-deterministic random
``FaultPlan`` to demonstrate quarantine + replay-exact recovery end to end.

Observability (``runtime.telemetry``): ``--telemetry`` turns on the engine's
lifecycle event ring + latency histograms and prints a TTFT/ITL percentile
summary; ``--trace-out FILE`` writes the run as a Chrome-trace JSON (open in
Perfetto / chrome://tracing — one track per decode slot plus queue /
allocator / scheduler tracks); ``--metrics-out FILE`` writes the counters and
histograms in Prometheus text exposition format. Both imply ``--telemetry``.
``--verbose`` prints one completion line per request (rid, tenant, class,
TTFT, ITL p50, tokens, outcome). Greedy token streams are bitwise identical
with telemetry on or off; the traced engine's UPIR program fingerprints
apart (``mm(traced)`` + ``upir.trace_emit``).

``--sequential`` also runs the old one-request-at-a-time path for comparison.
"""
import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=0,
                    help="KV horizon (default: prompt bucket + tokens)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples on-device")
    ap.add_argument("--top-k", type=int, default=0,
                    help="0 = full vocab; else sample the k largest logits")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="1.0 = off; else nucleus sampling: keep the "
                         "smallest set of tokens with cumulative prob >= p")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling PRNG seed (per-request keys fold in rid)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="finish requests on this token (-1 = run to budget)")
    ap.add_argument("--draft-config", default=None,
                    help="speculative decoding draft arch id ('self' = "
                         "self-speculation with the target's weights)")
    ap.add_argument("--lookahead", type=int, default=4,
                    help="draft tokens proposed per speculative step")
    ap.add_argument("--paged", action="store_true",
                    help="serve with the paged KV layout (page pool + page "
                         "tables; overcommit admission)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per physical KV page (paged layout)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic prefix caching (requires --paged): "
                         "identical prompt prefixes share ref-counted KV "
                         "pages copy-on-write and skip prefill compute; "
                         "token streams are unchanged bitwise")
    ap.add_argument("--policy", default="fifo",
                    choices=("fifo", "priority", "fair", "sjf"),
                    help="admission scheduling policy (runtime.scheduling)")
    ap.add_argument("--prefix-affinity", action="store_true",
                    help="admit requests whose prompt pages are already "
                         "prefix-cached first (requires --prefix-cache)")
    ap.add_argument("--tenant", default="default",
                    help="comma-separated tenant names cycled over requests; "
                         "'name:weight' entries set fair-policy weights")
    ap.add_argument("--priority", default="0",
                    help="comma-separated priority classes cycled over "
                         "requests (higher admits first under --policy "
                         "priority)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="TTFT SLO attached to every request (0 = none); "
                         "attainment is reported per class")
    ap.add_argument("--enforce-deadlines", action="store_true",
                    help="shed queued requests whose --deadline-ms SLO "
                         "already expired (typed SHED_DEADLINE outcome)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound the admission queue; overflow submissions "
                         "get a typed REJECTED_QUEUE_FULL (0 = unbounded)")
    ap.add_argument("--watchdog-ms", type=float, default=0.0,
                    help="per-iteration wall-clock watchdog: a step slower "
                         "than this quarantines the policy victim (0 = off)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="device-side finite guard on decode logits, polled "
                         "on the EOS cadence (no extra hot-loop syncs)")
    ap.add_argument("--debug-checks", action="store_true",
                    help="validate allocator/page-table invariants every "
                         "engine tick")
    ap.add_argument("--fault-seed", type=int, default=-1,
                    help="inject a seed-deterministic random FaultPlan "
                         "(-1 = no injection)")
    ap.add_argument("--fault-count", type=int, default=4,
                    help="faults in the random FaultPlan (--fault-seed)")
    ap.add_argument("--sequential", action="store_true",
                    help="also time the pre-engine one-at-a-time path")
    ap.add_argument("--telemetry", action="store_true",
                    help="record lifecycle events + TTFT/ITL histograms "
                         "(runtime.telemetry) and print a percentile "
                         "summary; the traced plan fingerprints apart")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="write the run as Chrome-trace JSON (Perfetto / "
                         "chrome://tracing); implies --telemetry")
    ap.add_argument("--metrics-out", default=None, metavar="FILE",
                    help="write counters + histograms in Prometheus text "
                         "format; implies --telemetry")
    ap.add_argument("--verbose", action="store_true",
                    help="one completion line per request (rid, tenant, "
                         "class, TTFT, ITL p50, tokens, outcome)")
    args = ap.parse_args()
    args.telemetry = args.telemetry or bool(args.trace_out) \
        or bool(args.metrics_out)

    import dataclasses

    import numpy as np

    import jax

    from .compile_cache import enable_compile_cache
    enable_compile_cache()

    from ..configs import config, smoke_config
    from ..models import api
    from ..runtime.engine import (Engine, EngineConfig, RequestSpec,
                                  serve_sequential)
    from ..runtime.faults import FaultPlan
    from ..runtime.sampling import SamplingParams
    from ..runtime.scheduling import SchedulingPolicy
    from ..runtime.speculative import SpecConfig

    cfg = smoke_config(args.arch) if args.smoke else config(args.arch)
    spec = api.family_spec(cfg)
    bucket = 1 << max(args.prompt_len - 1, 1).bit_length()
    max_seq = args.max_seq or bucket + args.tokens
    if args.temperature <= 0 and (args.top_k or args.seed
                                  or args.top_p < 1.0):
        ap.error("--top-k/--top-p/--seed only apply to sampled decode: "
                 "set --temperature > 0 (temperature 0 is greedy)")
    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed) \
        if args.temperature > 0 else None
    eos_id = args.eos_id if args.eos_id >= 0 else None

    params = api.init_params(cfg, jax.random.key(0))
    spec_decode = None
    draft_params = None
    if args.draft_config:
        if args.draft_config == "self":
            draft_cfg = dataclasses.replace(cfg, name=cfg.name + "-draft")
            draft_params = params
        else:
            draft_cfg = smoke_config(args.draft_config) if args.smoke \
                else config(args.draft_config)
        spec_decode = SpecConfig(draft_config=draft_cfg,
                                 lookahead_k=args.lookahead)
    if args.prefix_cache and not args.paged:
        ap.error("--prefix-cache requires --paged (prefix sharing is page "
                 "aliasing)")
    if args.prefix_affinity and not args.prefix_cache:
        ap.error("--prefix-affinity requires --prefix-cache (affinity admits "
                 "against the prefix index)")

    tenants, weights = [], {}
    for entry in args.tenant.split(","):
        name, _, w = entry.strip().partition(":")
        tenants.append(name)
        if w:
            weights[name] = float(w)
    classes = [int(c) for c in args.priority.split(",")]
    policy = SchedulingPolicy(
        kind=args.policy, prefix_affinity=args.prefix_affinity,
        tenant_weights=tuple(weights.items())
        if args.policy == "fair" else ())

    if args.enforce_deadlines and not args.deadline_ms:
        ap.error("--enforce-deadlines requires --deadline-ms (there is no "
                 "SLO to enforce otherwise)")
    fault_plan = None
    if args.fault_seed >= 0:
        # nan poisoning rides the plain decode step (spec engines verify
        # drafts instead); alloc_fail needs a page pool to exhaust
        kinds = ["exception", "stall"]
        if spec_decode is None:
            kinds.append("nan")
        if args.paged:
            kinds.append("alloc_fail")
        fault_plan = FaultPlan.random(args.fault_seed, n=args.fault_count,
                                      slots=args.slots, kinds=tuple(kinds))
        print(f"fault plan: {fault_plan.describe()}")

    engine = Engine(cfg, EngineConfig(slots=args.slots,
                                      prompt_buckets=(bucket,),
                                      max_seq=max_seq,
                                      kv_layout="paged" if args.paged
                                      else "dense",
                                      page_size=args.page_size,
                                      prefix_cache=args.prefix_cache,
                                      spec_decode=spec_decode,
                                      scheduling=policy,
                                      fault_plan=fault_plan,
                                      nan_guard=args.nan_guard,
                                      watchdog_ms=args.watchdog_ms or None,
                                      max_queue=args.max_queue or None,
                                      debug_checks=args.debug_checks,
                                      enforce_deadlines=args.enforce_deadlines,
                                      telemetry=args.telemetry),
                    params=params, draft_params=draft_params)

    rng = np.random.default_rng(0)

    def frames():
        if not spec.needs_encoder_memory:
            return None
        return (rng.normal(size=(cfg.encdec.enc_seq, cfg.d_model))
                * 0.02).astype(np.float32)

    def mk(prompt, tokens, i=0):
        return RequestSpec(
            prompt=tuple(prompt), max_new_tokens=tokens, sampling=sampling,
            eos_id=eos_id, encoder_input=frames(),
            tenant=tenants[i % len(tenants)],
            priority_class=classes[i % len(classes)],
            deadline_ms=args.deadline_ms or None)

    specs = [
        mk(rng.integers(0, cfg.vocab, size=args.prompt_len).tolist(),
           args.tokens, i)
        for i in range(args.requests)]

    # warm up (jit compile) outside the measured run
    engine.run([mk([1] * args.prompt_len, 2) for _ in range(args.slots)])
    engine.reset_stats()

    requests = engine.run(specs)
    st = engine.stats()
    mode = f"sampled(T={args.temperature},k={args.top_k},p={args.top_p})" \
        if sampling else "greedy"
    if spec_decode:
        mode += f"+spec(draft={spec_decode.draft_config.name}," \
                f"k={spec_decode.lookahead_k})"
    print(f"engine: arch={cfg.name} caps={','.join(st['capabilities']) or '-'} "
          f"requests={args.requests} slots={args.slots} "
          f"prompt={args.prompt_len} tokens={args.tokens} mode={mode} "
          f"policy={st['policy']}")
    print(f"  completed={st['completed']} eos_finished={st['eos_finished']} "
          f"rejected={st['rejected']} decode_steps={st['decode_steps']} "
          f"recycles={st['recycles']} preemptions={st['preemptions']}")
    if st["shed_deadline"] or st["rejected_queue_full"]:
        print(f"  shed_deadline={st['shed_deadline']} "
              f"rejected_queue_full={st['rejected_queue_full']}")
    if st.get("faults_injected") is not None:
        print(f"  faults_injected={st['faults_injected']} "
              f"quarantines={st['quarantines']} "
              f"recovered={st['recovered']} failed={st['failed']} "
              f"watchdog_trips={st['watchdog_trips']}")
        for f in st["failures"]:
            print(f"    FAILED rid={f.rid} kind={f.kind} "
                  f"retries={f.retries}: {f.detail}")
    if st.get("slo_attainment") is not None:
        by = " ".join(f"class{c}={v:.2f}"
                      for c, v in st["slo_by_class"].items())
        print(f"  slo_attainment={st['slo_attainment']:.2f} {by}")
    if spec_decode:
        print(f"  spec_steps={st['spec_steps']} "
              f"acceptance_rate={st['acceptance_rate']:.2f} "
              f"tokens_per_step="
              f"{st['tokens_generated'] / max(st['spec_steps'], 1):.2f}")
    if args.prefix_cache:
        print(f"  prefix_hits={st['prefix_hits']} "
              f"(full={st['prefix_full_hits']}) "
              f"hit_tokens={st['prefix_hit_tokens']} "
              f"cow_copies={st['cow_copies']} "
              f"cached_pages={st['prefix_cached_pages']} "
              f"shared_pages={st['shared_pages']}")
    print(f"  occupancy={st['batch_occupancy']:.2f} "
          f"throughput={st['tokens_per_s']:.1f} tok/s "
          f"plan_cache_hit_rate={st['plan_cache']['hit_rate']:.2f}")
    done = [r for r in requests if r.state == "done"]
    if done:
        print("  sample:", engine.finalize_request(done[0])[:16])

    if args.telemetry:
        tel = st["telemetry"]
        t, i = tel["ttft_ms"], tel["itl_ms"]
        print(f"  telemetry: events={tel['events']} "
              f"(dropped={tel['events_dropped']}) "
              f"ttft_ms p50={t.get('p50', 0):.1f} p95={t.get('p95', 0):.1f} "
              f"p99={t.get('p99', 0):.1f} "
              f"itl_ms p50={i.get('p50', 0):.1f} p95={i.get('p95', 0):.1f}")
        for c, h in sorted(tel["ttft_by_class_ms"].items()):
            print(f"    class {c}: ttft_ms p50={h.get('p50', 0):.1f} "
                  f"p95={h.get('p95', 0):.1f} n={h['count']}")
    if args.verbose:
        import statistics
        for r in requests:
            n = len(engine.finalize_request(r)) if r.state == "done" \
                else len(r.tokens_out)
            ttft = (r.t_first - r.t_submit) * 1e3 \
                if r.t_first and r.t_submit else float("nan")
            if r._itl_ms:
                itl = statistics.median(r._itl_ms)
            elif r.t_done and r.t_first and n > 1:
                itl = (r.t_done - r.t_first) * 1e3 / (n - 1)
            else:
                itl = float("nan")
            outcome = r.state if not r.reason else f"{r.state}({r.reason})"
            print(f"  rid={r.rid} tenant={r.tenant} class={r.priority_class} "
                  f"ttft_ms={ttft:.1f} itl_p50_ms={itl:.1f} tokens={n} "
                  f"outcome={outcome}")
    if args.trace_out:
        engine.telemetry.write_chrome_trace(args.trace_out)
        print(f"  chrome trace -> {args.trace_out} (open in Perfetto or "
              f"chrome://tracing)")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            f.write(engine.telemetry.to_prometheus_text())
        print(f"  prometheus metrics -> {args.metrics_out}")

    if args.sequential:
        seq = serve_sequential(cfg, params, requests, max_seq=max_seq,
                               prompt_buckets=(bucket,))
        print(f"sequential: throughput={seq['tokens_per_s']:.1f} tok/s "
              f"({st['tokens_per_s'] / max(seq['tokens_per_s'], 1e-9):.2f}x "
              f"engine speedup)")


if __name__ == "__main__":
    main()
