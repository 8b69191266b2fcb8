"""Batched serving driver.

LM serving (default):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-moe-30b-a3b \
      --smoke --batch 4 --prompt-len 64 --new-tokens 32

Non-Neural serving — any estimator registered in core/estimator.py goes
through the same NonNeuralServeEngine power-of-two bucket batching and the
kernels/dispatch.py registry:

  PYTHONPATH=src python -m repro.launch.serve --algo knn --batch 64 \
      --requests 256 --policy fp32

Sharded Non-Neural serving — ``--mesh N`` fits AND serves data-parallel
over an N-shard mesh axis (fit_sharded + the engine's sharded bucket
path, DESIGN.md §5).  N must not exceed the visible device count; on a
CPU box, force virtual devices BEFORE jax initialises:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve --algo kmeans --mesh 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ServeConfig
from repro.configs.registry import get_config, get_smoke_config
from repro.models import transformer
from repro.serving import ServeEngine


def serve_lm(args):
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(0)
    params = transformer.init_params(key, cfg)
    engine = ServeEngine(cfg, params, ServeConfig(
        max_seq=args.prompt_len + args.new_tokens))

    prompts = jax.random.randint(key, (args.batch, args.prompt_len),
                                 0, cfg.vocab_size)
    frontend = {}
    if cfg.encoder is not None:
        frontend["encoder_frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder.n_ctx, cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.02
    if cfg.vision is not None:
        frontend["patch_embeds"] = jax.random.normal(
            key, (args.batch, cfg.vision.num_patches, cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.02

    t0 = time.time()
    result = engine.generate(prompts, args.new_tokens,
                             temperature=args.temperature,
                             key=jax.random.PRNGKey(1), **frontend)
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"[serve] arch={cfg.arch_id} generated {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s) first row: {result.tokens[0][:8].tolist()}")
    return result


def serve_nonneural(args):
    """Fit one estimator and drive it through the bucketed engine — the
    unified serving path for all five Non-Neural pipelines."""
    from repro.core.estimator import make_fitted
    from repro.data.datasets import class_blobs
    from repro.kernels.dispatch import get_policy
    from repro.serving import NonNeuralServeEngine

    n_class = args.classes
    X, y = class_blobs(n=args.train_size + args.requests, d=args.dim,
                       n_class=n_class)
    X, Q = X[: args.train_size], X[args.train_size:]
    y, yq = y[: args.train_size], y[args.train_size:]

    mesh = None
    if args.mesh > 1:
        n_dev = len(jax.devices())
        if n_dev < args.mesh:
            raise SystemExit(
                f"--mesh {args.mesh} needs {args.mesh} devices, only "
                f"{n_dev} visible; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.mesh} "
                f"(before jax initialises) or run on a pod")
        from repro.launch.mesh import _mk
        mesh = _mk((args.mesh,), ("data",))

    extra = {}
    if args.algo == "ann":
        extra["nprobe"] = args.nprobe
        extra["refine"] = args.refine
        if args.cells is not None:
            extra["n_cells"] = args.cells
        if args.pq_m is not None:
            extra["pq_m"] = args.pq_m
    est = make_fitted(args.algo, X, y, n_groups=n_class,
                      policy=get_policy(args.policy), mesh=mesh, **extra)
    engine = NonNeuralServeEngine(est, max_batch=args.batch, mesh=mesh,
                                  policy=args.policy,
                                  strategy=args.strategy)
    if engine.quant_report:
        r = engine.quant_report
        ratio = r["bytes_fp32"] / max(r["bytes_int8"], 1)
        # GNB/GMM trade bytes for ops: their fp32 score tables are LARGER
        # than the moments they replace (the win there is the folded
        # div/log work, DESIGN.md §8) — report the direction honestly
        direction = f"{ratio:.2f}x smaller" if ratio >= 1.0 \
            else f"{1.0 / ratio:.2f}x larger (score tables trade bytes " \
                 f"for folded div/log work)"
        print(f"[quant] params {r['bytes_fp32']}B fp32 -> "
              f"{r['bytes_int8']}B int8 ({direction})")
    if args.stream:
        return serve_stream(args, engine, Q)
    engine.warmup(Q, autotune=args.autotune)
    if args.autotune and engine.tuned:
        arms = ", ".join(
            f"{b}->{a.strategy}/{a.path or a.static_path}"
            f"{f'/bn{a.bn}' if a.bn else ''}"
            f" ({a.us:.0f}us vs static {a.static_us:.0f}us)"
            + ("*" if a.differs else "")
            for b, a in sorted(engine.tuned.items()))
        print(f"[autotune] tuned arms (* = differs from static): {arms}")
    t0 = time.time()
    result = engine.classify(Q)
    jax.block_until_ready(result.classes)
    dt = time.time() - t0
    acc = float(jnp.mean(result.classes == jnp.asarray(yq))) \
        if args.algo in ("knn", "ann", "gnb", "rf") else float("nan")
    print(f"[serve] algo={args.algo} policy={args.policy} "
          f"shards={engine.n_shards} "
          f"served {args.requests} queries in {dt:.3f}s "
          f"({args.requests/dt:.0f} q/s, {result.launches} launches, "
          f"buckets={engine.bucket_launches}) acc={acc:.3f}")
    if engine.sharded:
        routes = ", ".join(f"{b}->{s}" for b, s in
                           sorted(engine.bucket_strategies.items()))
        print(f"[serve] strategy={args.strategy or 'auto'} routes: {routes}")
    return result


def serve_tenants(args):
    """--tenants G: fit G per-tenant estimators of the same shape, park
    them in a ModelStore (optionally capped to --resident-frac of the
    total fp32 bytes, the rest held int8 at rest), and serve them through
    ONE grouped vmapped launch per (group x bucket) cell instead of G
    separate launches (DESIGN.md §11)."""
    import numpy as np

    from repro.core.estimator import make_fitted
    from repro.data.datasets import class_blobs
    from repro.serving import ModelStore

    if args.algo == "ann":
        raise SystemExit("--tenants: ann has no grouped serving arm "
                         "(ragged IVF/PQ shapes, DESIGN.md §11)")
    if args.mesh > 1:
        raise SystemExit("--tenants is a single-device path; drop --mesh")

    G, d, n_class = args.tenants, args.dim, args.classes
    store = ModelStore()
    fits = []
    for t in range(G):
        X, y = class_blobs(n=args.train_size, d=d, n_class=n_class, seed=t)
        store.register(t, make_fitted(args.algo, X, y, n_groups=n_class))
        fits.append((X, y))
    full = store.stats()["resident_bytes"]
    if args.resident_frac < 1.0:
        store.set_budget(int(full * args.resident_frac))
    st = store.stats()
    budget = f"{st['budget_bytes']}B" if st["budget_bytes"] is not None \
        else "unbounded"
    print(f"[tenants] algo={args.algo} G={G} resident {st['n_resident']}/"
          f"{st['n_models']} ({st['resident_frac']:.2f} of models, budget="
          f"{budget} of {full}B fp32, "
          f"{st['at_rest_bytes']}B int8 at rest)")

    engine = store.make_engine(max_batch=args.batch, max_group=G)
    Q = np.stack([class_blobs(n=args.batch, d=d, n_class=n_class,
                              seed=1000 + t)[0] for t in range(G)])
    if args.stream:
        return serve_tenant_stream(args, store, engine, Q)

    ids = list(range(G))
    stacked, _gens = store.group(ids)
    engine.warmup_groups(stacked, d, g_sizes=[engine._group_bucket(G)],
                         b_sizes=[engine._bucket(args.batch)])
    t0 = time.time()
    res = engine.classify_group(stacked, Q)
    jax.block_until_ready(res.classes)
    dt_group = time.time() - t0

    jfn = jax.jit(store.template.predict_batch_fn())
    Qj = [jnp.asarray(Q[t]) for t in ids]
    outs = [jfn(store.params_of(t)[1], Qj[t]) for t in ids]
    jax.block_until_ready(outs)
    t0 = time.time()
    outs = [jfn(store.params_of(t)[1], Qj[t]) for t in ids]
    jax.block_until_ready(outs)
    dt_loop = time.time() - t0
    # conformance vs the SAME stacked lanes: under a budget the loop's
    # params_of() churns tenants through the lossy int8 round-trip
    from repro.core.estimator import unstack_params
    for t in ids:
        lane, _ = jfn(unstack_params(stacked, t), Qj[t])
        assert jnp.array_equal(res.classes[t], lane), t
    nq = G * args.batch
    print(f"[tenants] grouped {nq} queries ({G}x{args.batch}) in "
          f"{dt_group*1e3:.2f}ms ({dt_group/nq*1e6:.1f} us/q) vs per-model "
          f"loop {dt_loop*1e3:.2f}ms ({dt_loop/nq*1e6:.1f} us/q); "
          f"launches={dict(engine.group_launches)}; grouped classes "
          f"bit-equal to loop")
    return res


def _chaos_injector(args, store=None, n_tenants: int = 0):
    """--chaos PLAN: a named preset (runtime.chaos.PRESETS) seeded with
    --seed, or a path to a ChaosPlan JSON (the committed CI traces)."""
    if not args.chaos:
        return None
    from repro.runtime.chaos import PRESETS, ChaosInjector, ChaosPlan
    if args.chaos in PRESETS:
        plan = ChaosPlan.preset(args.chaos, seed=args.seed,
                                ticks=args.ticks, n_tenants=n_tenants)
    else:
        with open(args.chaos) as f:
            plan = ChaosPlan.from_json(f.read())
    print(f"[chaos] plan={args.chaos} seed={plan.seed} "
          f"stragglers={len(plan.straggler_ticks)} "
          f"nan={len(plan.nan_events)} storms={len(plan.storm_ticks)} "
          f"bursts={len(plan.burst)}")
    return ChaosInjector(plan, store=store)


def _print_robustness(sched):
    s = sched.stats.summary()
    if sched.stats.shed or sched.stats.downshifts or sched.stats.upshifts:
        print(f"[robust] shed={s['shed']} ({dict(sched.stats.shed_reasons)})"
              f"  shed_rate={s['shed_rate']:.3f}  "
              f"miss+shed={s['miss_plus_shed_rate']:.3f}  "
              f"downshifts={s['downshifts']} "
              f"upshifts={sched.stats.upshifts}  "
              f"tiers={dict(sched.stats.tier_launches)}")
    from collections import Counter
    kinds = Counter(e.kind for e in sched.events)
    if kinds:
        print(f"[robust] events: "
              + ", ".join(f"{k}={n}" for k, n in sorted(kinds.items())))
    # degradation must never be the thing that compiles: every launched
    # bucket sits in the INIT-TIME warmed snapshot of its own tier
    # (grouped schedulers record launch FOOTPRINTS per split level; their
    # no-compile invariant is the warmed_groups check the caller runs)
    if sched.store is None:
        for tier, per in sched.stats.tier_bucket_launches.items():
            assert set(per) <= set(sched.tier_warmed.get(tier, ())), \
                (tier, sorted(per), sorted(sched.tier_warmed.get(tier, ())))


def serve_tenant_stream(args, store, engine, Q):
    """--tenants --stream: cross-tenant Poisson arrivals coalesced by the
    store-mode RequestScheduler into (model-group x bucket) grouped
    launches; per-tenant SLO rows printed serving_table-style."""
    import numpy as np

    from repro.serving import RequestScheduler, poisson_trace, replay_trace

    G, d = Q.shape[0], Q.shape[2]
    ids = list(range(G))
    stacked, _gens = store.group(ids)
    engine.warmup_groups(stacked, d)
    degrade = None
    breaker = None
    if args.degrade:
        from repro.serving import BreakerConfig, DegradePolicy
        degrade = DegradePolicy(None, deadline=args.deadline)
        breaker = BreakerConfig()
    sched = RequestScheduler(engine, max_wait=args.max_wait,
                             cache_size=args.cache_size, store=store,
                             max_queue=args.max_queue,
                             shed_expired=args.degrade, degrade=degrade,
                             breaker=breaker)
    chaos = _chaos_injector(args, store=store, n_tenants=G)
    counts = poisson_trace(args.rate, args.ticks, seed=args.seed)
    flat = np.asarray(Q).reshape(-1, d)
    t0 = time.time()
    rids = replay_trace(sched, flat, counts, deadline=args.deadline,
                        model_ids=ids, chaos=chaos)
    dt = time.time() - t0
    s = sched.stats.summary()
    print(f"[tenants/stream] algo={args.algo} G={G} rate={args.rate} "
          f"ticks={args.ticks} max_wait={args.max_wait} "
          f"cache={args.cache_size}")
    print(f"[tenants/stream] served {len(rids)} requests in {dt:.3f}s wall "
          f"({s['launches']} grouped launches, cells="
          f"{dict(engine.group_launches)})")
    print(f"[tenants/stream] latency ticks p50={s['p50']:.0f} "
          f"p95={s['p95']:.0f} p99={s['p99']:.0f}  "
          f"throughput={s['throughput']:.2f} req/tick  "
          f"occupancy={s['occupancy']:.2f}  hit_rate={s['hit_rate']:.2f}  "
          f"deadline_miss={s['deadline_miss_rate']:.2f}")
    hdr = (f"{'tenant':>6} {'served':>6} {'p50':>5} {'p95':>5} "
           f"{'occupancy':>9} {'hit_rate':>8}")
    print(hdr)
    print("-" * len(hdr))
    for mid in sorted(sched.tenant_stats):
        ts = sched.tenant_stats[mid].summary()
        print(f"{mid:>6} {ts['served']:>6} {ts['p50']:>5.0f} "
              f"{ts['p95']:>5.0f} {ts['occupancy']:>9.2f} "
              f"{ts['hit_rate']:>8.2f}")
    _print_robustness(sched)
    assert set(engine.group_launches) <= engine.warmed_groups, \
        "stream compiled a new (group, bucket) cell mid-flight"
    return sched.stats


def serve_stream(args, engine, Q):
    """--stream: replay a Poisson-ish arrival trace (seeded rng) through
    the micro-batching RequestScheduler and report the SLO accounting
    (serving/scheduler.py; time is drain ticks, so the replay is
    deterministic for a given --seed)."""
    from repro.serving import RequestScheduler, poisson_trace, replay_trace

    engine.warmup_buckets(Q.shape[1], autotune=args.autotune)
    if args.autotune and engine.tuned:
        arms = ", ".join(
            f"{b}->{a.strategy}/{a.path or a.static_path}"
            + ("*" if a.differs else "")
            for b, a in sorted(engine.tuned.items()))
        print(f"[autotune] tuned arms (* = differs from static): {arms}")
    degrade = None
    if args.degrade:
        from repro.serving import DegradePolicy, build_ladder
        tiers = build_ladder(engine, Q.shape[1])
        degrade = DegradePolicy(tiers, deadline=args.deadline)
        print(f"[degrade] ladder: "
              + " -> ".join(f"{t.name} (x{t.capacity_factor})"
                            for t in tiers))
    sched = RequestScheduler(engine, max_wait=args.max_wait,
                             cache_size=args.cache_size,
                             max_queue=args.max_queue,
                             shed_expired=args.degrade, degrade=degrade)
    chaos = _chaos_injector(args)
    counts = poisson_trace(args.rate, args.ticks, seed=args.seed)
    t0 = time.time()
    ids = replay_trace(sched, Q, counts, deadline=args.deadline,
                       chaos=chaos)
    dt = time.time() - t0
    s = sched.stats.summary()
    print(f"[stream] algo={args.algo} policy={args.policy} "
          f"shards={engine.n_shards} rate={args.rate} ticks={args.ticks} "
          f"max_wait={args.max_wait} cache={args.cache_size}")
    n_strag = sum(e.kind.startswith("straggler_") for e in sched.events)
    print(f"[stream] served {len(ids)} requests in {dt:.3f}s wall "
          f"({s['launches']} launches, buckets={engine.bucket_launches}, "
          f"straggler events={n_strag})")
    print(f"[stream] latency ticks p50={s['p50']:.0f} p95={s['p95']:.0f} "
          f"p99={s['p99']:.0f}  throughput={s['throughput']:.2f} req/tick  "
          f"occupancy={s['occupancy']:.2f}  hit_rate={s['hit_rate']:.2f}  "
          f"deadline_miss={s['deadline_miss_rate']:.2f}")
    _print_robustness(sched)
    assert set(engine.bucket_launches) <= sched.warmed, \
        "stream compiled a new bucket mid-flight"
    return sched.stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--algo", default="lm",
                    choices=["lm", "knn", "ann", "kmeans", "gnb", "gmm",
                             "rf"],
                    help="lm = transformer serving; otherwise a Non-Neural "
                         "estimator through NonNeuralServeEngine (ann = "
                         "IVF+PQ approximate kNN, DESIGN.md §10)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--policy", default="fp32",
                    help="PrecisionPolicy name: fp32, bf16, int8 (the "
                         "quantized serving tier, DESIGN.md §8), or "
                         "<dtype>@<cost_backend> (e.g. fp32@libgcc)")
    ap.add_argument("--mesh", type=int, default=1,
                    help="shard count for data-parallel Non-Neural "
                         "fit/serve (1 = single-device); needs that many "
                         "visible devices")
    ap.add_argument("--strategy", default=None,
                    choices=["auto", "single", "query", "reference"],
                    help="sharded serving partition strategy (DESIGN.md "
                         "§9): auto = per-bucket cost model (default), "
                         "query = batch rows sharded / replicated model, "
                         "reference = model axis sharded + merge "
                         "collective, single = one device")
    ap.add_argument("--autotune", action="store_true",
                    help="micro-time every registered arm (path / block "
                         "size / sharding strategy) per warmed bucket and "
                         "route launches through the measured winner "
                         "instead of the analytic selector (paper §5.2 "
                         "profile-then-optimize; DESIGN.md §12)")
    ap.add_argument("--calibration", default=None, metavar="PATH",
                    help="CALIBRATION.json to load into the cost model so "
                         "path and strategy selection use measured "
                         "us-per-op vectors instead of the analytic "
                         "literature-seeded ones (see "
                         "repro.core.calibrate; also honoured via the "
                         "REPRO_CALIBRATION env var)")
    ap.add_argument("--stream", action="store_true",
                    help="replay a Poisson-ish request stream through the "
                         "micro-batching RequestScheduler instead of one "
                         "pre-formed batch (Non-Neural algos only)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="--stream mean arrivals per drain tick")
    ap.add_argument("--ticks", type=int, default=64,
                    help="--stream trace length in drain ticks")
    ap.add_argument("--max-wait", type=int, default=4,
                    help="--stream coalescing window in drain ticks")
    ap.add_argument("--cache-size", type=int, default=0,
                    help="--stream LRU result cache entries (0 = off)")
    ap.add_argument("--deadline", type=int, default=None,
                    help="--stream per-request SLO in drain ticks")
    ap.add_argument("--seed", type=int, default=0,
                    help="--stream arrival-trace rng seed")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="--stream admission-control bound: submits "
                         "beyond this many queued requests shed with "
                         "reason=queue_full (default unbounded)")
    ap.add_argument("--degrade", action="store_true",
                    help="--stream graceful degradation: deadline-"
                         "enforced shedding plus the brownout ladder "
                         "(fp32 -> int8 -> ANN siblings of the same "
                         "model; --tenants streams split the grouped "
                         "launch and arm per-tenant circuit breakers "
                         "instead; serving/degrade.py)")
    ap.add_argument("--chaos", default=None, metavar="PLAN",
                    help="--stream deterministic fault injection: a "
                         "preset name (burst, straggler, storm, mixed) "
                         "seeded with --seed, or a path to a ChaosPlan "
                         "JSON (runtime/chaos.py)")
    ap.add_argument("--nprobe", type=int, default=4,
                    help="--algo ann: IVF cells probed per query (more = "
                         "higher recall, more ADC work)")
    ap.add_argument("--cells", type=int, default=None,
                    help="--algo ann: IVF cell count (default ~sqrt(N), "
                         "capped at 64)")
    ap.add_argument("--pq-m", type=int, default=None,
                    help="--algo ann: PQ subspace count")
    ap.add_argument("--refine", type=int, default=0,
                    help="--algo ann: exact re-rank of the ADC top-R "
                         "survivors (0 = pure ADC ranking)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="serve G same-shape per-tenant fits from a "
                         "ModelStore through grouped vmapped launches "
                         "(Non-Neural algos except ann; DESIGN.md §11)")
    ap.add_argument("--resident-frac", type=float, default=1.0,
                    help="--tenants: fraction of total fp32 param bytes "
                         "kept resident; the LRU tail is held int8 at "
                         "rest and dequantized on admit")
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--train-size", type=int, default=400)
    ap.add_argument("--dim", type=int, default=21)
    ap.add_argument("--classes", type=int, default=3)
    args = ap.parse_args(argv)
    if args.calibration:
        from repro.core.precision import CostModel
        from repro.kernels import dispatch
        dispatch.set_cost_model(CostModel.from_calibration(args.calibration))
        print(f"[calibrate] cost model loaded from {args.calibration}")
    if args.algo == "lm":
        return serve_lm(args)
    if args.tenants > 1:
        return serve_tenants(args)
    return serve_nonneural(args)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
