"""Subgraph-counting launcher: the paper's workload end to end.

``python -m repro.launch.count --config bench-small --mode adaptive``

Resolves the configured graph (synthesized RMAT, or a real dataset via
``--graph edges.txt|graph.npz``) into a ``repro.api.CountRequest`` and runs
it through the unified ``Counter`` facade: the same key-based contract,
on-device coloring sampling, and (eps, delta) estimator on BOTH backends.

``--mode single`` (or the default on a single-device host) drives the
in-core batched/fused engine (``--batch``/``--fuse``/``--spmm-kind``);
any other mode drives the shard_map engine with that exchange schedule.
``--templates u3-1,u5-2,u7-2`` (or a config row with a ``templates``
family) counts the whole family in ONE pass per coloring over the shared
subtree DAG (``Counter.estimate_many``) and reports per-template
estimates plus the unique-table reuse the compiled DAG achieved.
Either way the report comes from one place — the shared estimator — so the
median-of-means (over ``log(1/delta)`` groups), mean, and RSD are computed
identically no matter where the counting ran.  Compilation is warmed
outside the timer via ``counter.sample_fn``, so the printed wall-clock is
pure counting.
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.api import Counter
from repro.configs import COUNTING_CONFIGS
from repro.core import load_edge_file, load_npz
from repro.core.count_engine import node_kernels
from repro.core.estimator import num_groups_for
from repro.core.templates import TEMPLATES
from repro.launch.compile_cache import use_compile_cache


def _plan_report(plan):
    """Surface the signals the plan's adaptive choices used: the spmm auto
    patch density, the kernel each node's ops run (chosen from the node's
    table shapes against the kernels' VMEM limit), and the per-node table
    densities / capacities of active-frontier compaction (§15)."""
    spmm = getattr(plan, "spmm_plan", None)
    if spmm is not None and spmm.patch_density is not None:
        print(f"spmm auto: {spmm.patch_density:.1f} edges/patch "
              f"-> kind={spmm.kind}")
    if spmm is not None:
        for i, choice in sorted(node_kernels(plan).items()):
            print(f"  node {i}: {choice}")
    spec = getattr(plan, "compaction", None)
    if spec is None:
        return
    dens = " ".join(f"n{i}={spec.density[i]:.3f}" for i in sorted(spec.density))
    caps = {}
    for tag, m in (("combine", spec.combine_caps),
                   ("table", spec.table_caps),
                   ("exchange", spec.exchange_caps),
                   ("ring", spec.shard_caps)):
        for i, c in sorted(m.items()):
            caps[f"{tag}[{i}]"] = c
    print(f"compaction: threshold {spec.threshold} node densities: {dens}")
    print(f"compaction caps: {caps if caps else 'none engaged'}")


def _route_report(counter, request):
    """Exchange-routing provenance (§18): per-node schedule choices and
    the cost model behind them — calibrated when --adaptive measured."""
    from repro.core.distributed import plan_route_report

    opts = request.plan_opts
    rep = plan_route_report(
        counter.plan,
        mode=opts.get("mode", "adaptive"),
        group_factor=opts.get("group_factor", 1),
        wire_dtype=opts.get("wire_dtype", "float32"),
        adaptive=opts.get("adaptive", "model"),
        mesh=counter._mesh,
        data_axis=opts.get("data_axis", "data"),
    )
    m = rep["model"]
    src = "calibrated" if rep["calibrated"] else "assumed"
    print(f"routing: wire={rep['wire_dtype']} {src} model "
          f"alpha={m['alpha']:.3g}s beta={m['beta']:.3g}s/B "
          f"flops={m['flops_per_s']:.3g}/s")
    for i, row in sorted(rep["per_node"].items()):
        print(f"  node {i}: {row['mode']:<8} "
              f"a2a {row['a2a_bytes'] / 1e6:.3f} MB "
              f"ring {row['ring_bytes'] / 1e6:.3f} MB "
              f"predicted {row['predicted_s'] * 1e6:.1f} us")


def _robust_report(res):
    """Recovery provenance: what was restored, what was given up on."""
    if res.resumed_from:
        print(f"resumed: {res.resumed_from} colorings restored from "
              f"checkpoint (progress/RSD include them)")
    for q in res.quarantined:
        print(f"quarantined: {q}")


def _report(label, shards, res, dt, ran):
    # the timer covers every coloring that actually executed (the last
    # batched dispatch may overshoot --iters); the statistics use --iters
    print(f"mode={label} shards={shards}: {ran} colorings in {dt:.2f}s "
          f"({dt / max(ran, 1) * 1e3:.1f} ms/coloring)")
    groups = num_groups_for(res.delta, res.niter)
    print(f"estimate (median-of-means, {groups} groups): {res.estimate:.6g}")
    print(f"estimate (mean)           : {res.mean:.6g}  RSD {res.relative_sd:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="bench-small", choices=sorted(COUNTING_CONFIGS))
    ap.add_argument("--graph", default=None, metavar="PATH",
                    help="real dataset (.npz from save_npz, else an edge-list "
                         "text file); default: synthesize the config's RMAT")
    # default None means "unset": pick the backend from the device count
    # and the exchange schedule from the config row
    ap.add_argument("--mode", default=None,
                    choices=["alltoall", "pipeline", "adaptive", "ring",
                             "single"])
    ap.add_argument("--templates", default=None, metavar="A,B,C",
                    help="comma-separated template family (trees AND "
                         "treewidth-2 names like cycle5,diamond): count them "
                         "all in ONE pass over the shared subtree DAG "
                         "(Counter.estimate_many); names are validated "
                         "against the registry; default: the config's "
                         "family, else its single template")
    ap.add_argument("--iters", type=int, default=16)
    ap.add_argument("--delta", type=float, default=0.1)
    ap.add_argument("--group-factor", type=int, default=1)
    ap.add_argument("--batch", type=int, default=8,
                    help="colorings per jit dispatch (both backends)")
    ap.add_argument("--fuse", action="store_true",
                    help="fused SpMM->combine: never materializes the "
                         "neighbor sum M (both backends)")
    ap.add_argument("--impl", default=None, choices=["auto", "xla", "pallas"],
                    help="kernel routing (both backends; default: "
                         "backend-appropriate)")
    ap.add_argument("--spmm-kind", default="auto", choices=["auto", "edges", "blocks"])
    ap.add_argument("--bucket-tile", type=int, default=128,
                    help="distributed §3.3 task size: edges per bucket tile")
    ap.add_argument("--compact", action="store_true", default=None,
                    help="active-frontier compaction (§15): probe per-node "
                         "table densities and compact tables/exchange below "
                         "--density-threshold (both backends)")
    ap.add_argument("--density-threshold", type=float, default=None,
                    help="compact a node once its active-row fraction is at "
                         "or below this (default: config row's)")
    ap.add_argument("--capacity-factor", type=float, default=None,
                    help="capacity headroom over the probed active maximum "
                         "before the dense overflow fallback")
    ap.add_argument("--wire-dtype", default=None,
                    choices=["float32", "int16", "int8"],
                    help="narrow-wire exchange (§18): ship distributed "
                         "exchange slabs at this width, exactness kept by "
                         "saturation checking + wider-wire redispatch")
    ap.add_argument("--adaptive", default=None,
                    choices=["model", "measured"],
                    help="adaptive router cost model: assumed link constants "
                         "or a one-shot calibration probe at plan build")
    # robustness (DESIGN.md §16): estimator state survives kills and flaky
    # shards; a killed run resumed via --resume returns the bit-identical
    # estimate an uninterrupted run produces
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="persist estimator state (atomic, checksummed) "
                         "under DIR every --checkpoint-every colorings")
    ap.add_argument("--resume", default=None, metavar="DIR",
                    help="resume from the latest readable checkpoint in DIR "
                         "(implies --checkpoint-dir DIR); bit-exact vs an "
                         "uninterrupted run with the same seed")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint cadence in colorings (default: every "
                         "batch when a checkpoint dir is set)")
    ap.add_argument("--max-retries", type=int, default=None,
                    help="supervise the sample pipeline: retry transient "
                         "per-batch faults up to N times with backoff, then "
                         "quarantine the batch and report it")
    ap.add_argument("--target-rsd", type=float, default=None,
                    help="stop early once the running relative standard "
                         "error of the mean reaches this (resume-aware)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.batch < 1:
        ap.error(f"--batch must be >= 1 (got {args.batch})")
    cache_dir = use_compile_cache()
    dev = jax.devices()[0]
    print(f"devices: {jax.device_count()} x {dev.platform} ({dev.device_kind}); "
          f"compile cache {cache_dir}")
    ckpt_dir = args.resume or args.checkpoint_dir
    ckpt_every = args.checkpoint_every or (args.batch if ckpt_dir else 0)
    robust_kw = dict(
        checkpoint=ckpt_dir,
        checkpoint_every=ckpt_every,
        resume=bool(args.resume),
        max_retries=args.max_retries,
        target_rsd=args.target_rsd,
    )

    ccfg = COUNTING_CONFIGS[args.config]
    _family_arg = None
    if args.templates:
        # fail fast, before any graph is synthesized or plan compiled:
        # unknown/duplicate names are a typo, not a workload
        _family_arg = [s.strip() for s in args.templates.split(",") if s.strip()]
        unknown = [s for s in _family_arg if s not in TEMPLATES]
        if unknown:
            ap.error(
                f"unknown template(s) {', '.join(sorted(set(unknown)))}; "
                f"registry has: {', '.join(sorted(TEMPLATES))}"
            )
        dups = sorted({s for s in _family_arg if _family_arg.count(s) > 1})
        if dups:
            ap.error(f"duplicate template(s) in --templates: {', '.join(dups)}")
        if not _family_arg:
            ap.error("--templates is empty after parsing")
    if args.graph:
        g = load_npz(args.graph) if args.graph.endswith(".npz") else load_edge_file(args.graph)
        print(f"loaded {g.name}: V={g.n} E={g.num_edges} skew={g.skewness():.0f}")
    else:
        print(f"synthesizing RMAT: V={ccfg.num_vertices} E={ccfg.num_edges} "
              f"skew={ccfg.skew}")
        g = ccfg.synthesize()

    single = args.mode == "single" or (args.mode is None and jax.device_count() == 1)
    impl_opt = {"impl": args.impl} if args.impl else {}
    for name, val in (("compact", args.compact),
                      ("density_threshold", args.density_threshold),
                      ("capacity_factor", args.capacity_factor),
                      ("wire_dtype", args.wire_dtype),
                      ("adaptive", args.adaptive)):
        if val is not None:
            impl_opt[name] = val
    if single:
        # a block-dense plan has no edge slabs, so fused_count would fall
        # back to the unfused path: when fusing, steer 'auto' to 'edges'
        spmm_kind = args.spmm_kind
        if args.fuse and spmm_kind == "auto":
            spmm_kind = "edges"
        request = ccfg.to_request(
            g,
            backend="single",
            n_iter=args.iters,
            delta=args.delta,
            batch=args.batch,
            spmm_kind=spmm_kind,
            fuse=args.fuse,
            **impl_opt,
        )
    else:
        request = ccfg.to_request(
            g,
            backend="distributed",
            n_iter=args.iters,
            delta=args.delta,
            batch=args.batch,
            mode=args.mode or ccfg.mode,
            group_factor=args.group_factor,
            fuse=args.fuse,
            bucket_tile=args.bucket_tile,
            **impl_opt,
        )
    counter = Counter.from_request(request)
    key = jax.random.key(args.seed)
    family = _family_arg if args.templates else list(ccfg.templates)
    ran = -(-args.iters // args.batch) * args.batch
    if family:
        # family mode never builds the single-template plan (the label comes
        # from the request, not from counter.plan): one shared-DAG pass per
        # coloring does all the counting
        if single:
            shards = 1
            label = f"single(batch={args.batch},fuse={args.fuse})"
        else:
            shards = min(request.plan_opts["num_shards"], jax.device_count())
            label = (f"{request.plan_opts['mode']}(fuse={args.fuse},"
                     f"impl={args.impl or 'xla'})")
        # warm the jit at the REAL batch size (both backends cache compiled
        # programs per batch), so compile stays outside the timer
        b = request.batch or min(8, request.n_iter)
        counter.estimate_many(family, n_iter=b, key=key, batch=b)
        t0 = time.perf_counter()
        res = counter.estimate_many(
            family,
            n_iter=request.n_iter,
            delta=request.delta,
            key=key,
            batch=request.batch,
            **robust_kw,
        )
        dt = time.perf_counter() - t0
        _robust_report(res)
        print(f"mode={label} shards={shards}: family of {len(res)} templates, "
              f"k={res.k}, {res.unique_tables} unique tables "
              f"(vs {res.chain_tables} chain nodes), {ran} colorings in "
              f"{dt:.2f}s ({dt / max(ran, 1) * 1e3:.1f} ms/coloring)")
        groups = num_groups_for(res.delta, res.niter)
        for one in res:
            print(f"  {one.template:>10}: median-of-means {one.estimate:.6g} "
                  f"({groups} groups)  mean {one.mean:.6g} "
                  f"RSD {one.relative_sd:.2f}")
        return
    if single:
        shards = 1
        # fusion needs the edge-slab layout; report whether it engaged
        fused = args.fuse and counter.plan.spmm_plan.slab_dst is not None
        label = (f"single(batch={args.batch},fuse={fused},"
                 f"spmm={counter.plan.spmm_plan.kind})")
    else:
        shards = counter.plan.num_shards
        label = (f"{request.plan_opts['mode']}(fuse={args.fuse},"
                 f"impl={args.impl or 'xla'},"
                 f"tile={counter.plan.bucket_tile}x{counter.plan.num_tiles})")
    _plan_report(counter.plan)
    if not single:
        _route_report(counter, request)
    counter.sample_fn(key, args.batch)  # compile outside the timer
    t0 = time.perf_counter()
    res = counter.estimate(
        n_iter=request.n_iter,
        delta=request.delta,
        key=key,
        batch=request.batch,
        **robust_kw,
    )
    dt = time.perf_counter() - t0
    _robust_report(res)
    _report(label, shards, res, dt, ran)


if __name__ == "__main__":
    main()
