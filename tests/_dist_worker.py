"""Worker script for multi-device tests (run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8).

Prints one line per check: ``CHECK <name> PASS|FAIL <details>``.
Exit code 0 iff all checks pass.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compat import make_mesh  # noqa: E402

FAILURES = []


def check(name, ok, details=""):
    print(f"CHECK {name} {'PASS' if ok else 'FAIL'} {details}")
    if not ok:
        FAILURES.append(name)


def test_ring_collectives():
    from repro.comm import (
        compressed_ring_reduce_scatter,
        ring_allgather,
        ring_allgather_overlap,
        ring_reduce_scatter,
    )

    mesh = make_mesh((8,), ("x",))
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 4, 16)).astype(np.float32)

    # ring all-gather == lax.all_gather
    f = jax.jit(
        shard_map(
            lambda a: ring_allgather(a[0], "x"),
            mesh=mesh,
            in_specs=P("x"),
            out_specs=P("x"),
        )
    )
    got = np.asarray(f(x))  # [8(dev), 8, 4... wait shapes
    want = np.broadcast_to(x[None], (8,) + x.shape).reshape(8 * 8, 4, 16)
    check("ring_allgather", np.allclose(got.reshape(8 * 8, 4, 16), want))

    # overlap consume: acc += chunk * (src+1) must equal sum_q (q+1)*x_q
    def run(a):
        def combine(acc, chunk, src):
            return acc + chunk * (src + 1).astype(jnp.float32)

        return ring_allgather_overlap(a[0], "x", combine, jnp.zeros_like(a[0]))[None]

    f = jax.jit(shard_map(run, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(x))
    want_each = sum((q + 1) * x[q] for q in range(8))
    check(
        "ring_allgather_overlap",
        np.allclose(got, np.broadcast_to(want_each, (8, 4, 16)), atol=1e-5),
    )

    # ring reduce-scatter == psum then slice
    xs = rng.standard_normal((8, 8, 4, 16)).astype(np.float32)  # [dev, chunk, ...]

    def rs(a):
        return ring_reduce_scatter(a[0], "x")[None]

    f = jax.jit(shard_map(rs, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(xs))
    want = xs.sum(axis=0)  # [chunk, 4, 16]; device p gets chunk p
    check(
        "ring_reduce_scatter",
        np.allclose(got, want, atol=1e-4),
        f"max err {np.abs(got - want).max():.2e}",
    )

    def crs(a):
        return compressed_ring_reduce_scatter(a[0], "x")[None]

    f = jax.jit(shard_map(crs, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    got = np.asarray(f(xs))
    rel = np.abs(got - want).max() / np.abs(want).max()
    check("compressed_ring_reduce_scatter", rel < 0.05, f"rel err {rel:.3f}")


def test_grouped_exchange():
    from repro.comm import fused_exchange, grouped_exchange

    mesh = make_mesh((8,), ("x",))
    rng = np.random.default_rng(1)
    # chunks[p, q] = payload device p holds for device q
    chunks = rng.standard_normal((8, 8, 4)).astype(np.float32)

    def run(mode, g=1):
        def consume(acc, chunk, src):
            w = (jnp.asarray(src) + 1).astype(jnp.float32)
            return acc + chunk * w

        def body(a):
            init = jnp.zeros((4,), jnp.float32)
            if mode == "fused":
                return fused_exchange(a[0], "x", consume, init)[None]
            return grouped_exchange(a[0], "x", consume, init, group_factor=g)[None]

        f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
        return np.asarray(f(chunks))

    want = np.stack([sum((q + 1) * chunks[q, p] for q in range(8)) for p in range(8)])
    got_f = run("fused")
    check("fused_exchange", np.allclose(got_f, want, atol=1e-5))
    for g in (1, 2, 3, 7):
        got_g = run("grouped", g)
        check(f"grouped_exchange_g{g}", np.allclose(got_g, want, atol=1e-5))


def test_distributed_counting():
    from repro.core import erdos_renyi
    from repro.core.brute_force import count_colorful_maps
    from repro.core.distributed import (
        build_distributed_plan,
        make_count_fn,
        shard_coloring,
    )
    from repro.core.templates import path_tree, spider_tree

    g = erdos_renyi(97, 5.0, seed=7)  # ragged shard sizes on purpose
    rng = np.random.default_rng(3)

    for tree, tname in ((path_tree(4), "p4"), (spider_tree([2, 1]), "sp21")):
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        want = count_colorful_maps(g, tree, coloring)

        for shards, iters in ((4, 2), (8, 1)):
            mesh_names = ("data", "model") if iters > 1 else ("data",)
            mesh_shape = (shards, iters) if iters > 1 else (shards,)
            mesh = make_mesh(mesh_shape, mesh_names)
            plan = build_distributed_plan(g, tree, shards)
            cols = shard_coloring(plan, coloring)[None]  # [1, P, n_loc_pad]
            if iters > 1:
                cols = np.broadcast_to(cols, (iters,) + cols.shape[1:])
            for mode, gf in (
                ("alltoall", 1),
                ("pipeline", 1),
                ("pipeline", 3),
                ("adaptive", 1),
                ("ring", 1),
            ):
                f = make_count_fn(
                    plan,
                    mesh,
                    mode=mode,
                    iter_axis="model" if iters > 1 else None,
                    group_factor=gf,
                )
                got = np.asarray(f(jnp.asarray(cols)))
                ok = np.allclose(got, want, rtol=1e-6)
                check(
                    f"dist_{tname}_P{shards}I{iters}_{mode}_g{gf}",
                    ok,
                    f"got {got[0]} want {want}",
                )


def test_tiled_skew_parity():
    """RMAT skew-8 graph, 8 shards: distributed vs brute force across all
    four exchange modes on the §3.3 tiled bucket layout, with the fused
    (never-materialize-M) and Pallas kernel routings; plus a structural
    jaxpr scan asserting no [P, P, max_e]-shaped bucket array survives in
    the traced count program."""
    from repro.core import rmat
    from repro.core.brute_force import count_colorful_maps
    from repro.core.distributed import (
        build_distributed_plan,
        make_count_fn,
        shard_coloring,
    )
    from repro.core.templates import path_tree
    from repro.kernels import ops

    g = rmat(1024, 12_000, skew=8, seed=2)  # contiguous shards: heavy skew
    tree = path_tree(4)
    rng = np.random.default_rng(9)
    coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
    want = count_colorful_maps(g, tree, coloring)
    mesh = make_mesh((8,), ("data",))
    plan = build_distributed_plan(g, tree, 8)
    max_e_pad = max(
        ops.pad_to(int(plan.bucket_counts.max()), plan.bucket_tile),
        plan.bucket_tile,
    )
    check("tiled_plan_no_global_max",
          all(a.shape[2] < max_e_pad for a in plan.device_arrays
              if a.ndim == 3 and a.shape[:2] == (8, 8)),
          f"max_e_pad={max_e_pad}")
    cols = jnp.asarray(shard_coloring(plan, coloring)[None])

    for mode in ("alltoall", "pipeline", "adaptive", "ring"):
        for fuse in (False, True):
            f = make_count_fn(plan, mesh, mode=mode, fuse=fuse)
            got = np.asarray(f(cols))
            ok = np.allclose(got, want, rtol=1e-6)
            check(f"skew8_{mode}_fuse{int(fuse)}", ok, f"got {got[0]} want {want}")
    # Pallas routing: the edge-tile / fused kernels over the exchange
    # buffer (alltoall) and the Pallas combine on the incremental modes
    for mode, fuse in (("alltoall", False), ("alltoall", True),
                       ("pipeline", True), ("ring", False)):
        f = make_count_fn(plan, mesh, mode=mode, fuse=fuse, impl="pallas")
        got = np.asarray(f(cols))
        ok = np.allclose(got, want, rtol=1e-6)
        check(f"skew8_{mode}_fuse{int(fuse)}_pallas", ok, f"got {got[0]} want {want}")

    # structural: no traced value in the count program has the seed's
    # [P, P, max_e] global-max bucket shape (or anything at least as wide)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from test_kernels import _iter_eqns

    for mode in ("pipeline", "alltoall", "ring"):
        f = make_count_fn(plan, mesh, mode=mode)
        jaxpr = jax.make_jaxpr(f)(cols)
        bad = [
            tuple(v.aval.shape)
            for e in _iter_eqns(jaxpr.jaxpr)
            for v in list(e.outvars) + [a for a in e.invars if hasattr(a, "aval")]
            if len(getattr(v.aval, "shape", ())) == 3
            and v.aval.shape[:2] == (8, 8)
            and v.aval.shape[2] >= max_e_pad
        ]
        check(f"jaxpr_no_global_max_{mode}", not bad, f"found {bad[:3]}")


def test_unified_api():
    """Counter facade over 8 real shards: fixed-coloring parity with the
    single-device backend, and the keyed on-device sampling path agreeing
    with the brute-force oracle through the shared estimator."""
    from repro.api import Counter
    from repro.core import erdos_renyi
    from repro.core.brute_force import count_colorful_maps, count_copies
    from repro.core.distributed import make_count_fn
    from repro.core.templates import path_tree, spider_tree

    g = erdos_renyi(97, 5.0, seed=7)  # ragged shard sizes on purpose
    rng = np.random.default_rng(11)

    # parity: single vs 8-shard distributed on a fixed coloring
    for tree, tname in ((path_tree(4), "p4"), (spider_tree([2, 1]), "sp21")):
        coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
        want = count_colorful_maps(g, tree, coloring)
        single = Counter.from_graph(g, tree, backend="single")
        dist = Counter.from_graph(g, tree, backend="distributed", num_shards=8, mode="adaptive")
        got_s = single.count_coloring(coloring)
        got_d = dist.count_coloring(coloring)
        ok = np.allclose([got_s, got_d], want, rtol=1e-6)
        check(f"api_parity_{tname}_P8", ok, f"single {got_s} dist {got_d} want {want}")

    # keyed estimate: on-device coloring sampling, estimator vs oracle
    tree = path_tree(3)
    truth = count_copies(g, tree)
    dist = Counter.from_graph(g, tree, backend="distributed", num_shards=8, mode="pipeline")
    res = dist.estimate(n_iter=192, key=jax.random.key(0), batch=32)
    rel = abs(res.mean - truth) / truth
    check("api_keyed_estimate_P8", rel < 0.25,
          f"mean {res.mean:.1f} truth {truth:.1f} rel {rel:.2f}")

    # keyed fn over a 4x2 mesh: iteration axis shards the keys
    from repro.core.distributed import build_distributed_plan

    mesh = make_mesh((4, 2), ("data", "model"))
    plan4 = build_distributed_plan(g, tree, 4)
    fk = make_count_fn(plan4, mesh, mode="ring", iter_axis="model", keyed=True)
    counts = np.asarray(fk(jax.random.split(jax.random.key(5), 6)))
    ests = counts * plan4.scale
    rel = abs(ests.mean() - truth) / truth
    check("api_keyed_iter_axis", counts.shape == (6,) and rel < 0.6,
          f"ests mean {ests.mean():.1f} truth {truth:.1f}")

    # facade over an explicit 4x2 mesh: num_shards derived from the data
    # axis, count_coloring replicated over the iter axis, estimate rounding
    # an odd batch up to the iter-axis multiple
    fc = Counter.from_graph(
        g, tree, backend="distributed", mesh=mesh, iter_axis="model",
        mode="pipeline",
    )
    coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
    want = count_colorful_maps(g, tree, coloring)
    got = fc.count_coloring(coloring)
    check("api_mesh_count_coloring", np.allclose(got, want), f"got {got} want {want}")
    res = fc.estimate(n_iter=5, key=jax.random.key(6), batch=5)  # 5 % 2 != 0
    rel = abs(res.mean - truth) / truth
    check("api_mesh_estimate_odd_batch",
          res.niter == 5 and len(res.samples) == 5 and rel < 1.0,
          f"mean {res.mean:.1f} truth {truth:.1f}")


def test_multi_template():
    """Family counting over 8 real shards: one shared-DAG pass per coloring.

    Fixed-coloring parity against the brute-force oracle per template for
    all four exchange modes x fuse, plus keyed estimate_many parity: with
    the same key, per-template keyed runs (n_colors = k) must reproduce the
    family run's sample columns exactly.
    """
    from repro.api import Counter
    from repro.core import erdos_renyi
    from repro.core.brute_force import count_colorful_maps
    from repro.core.templates import path_tree, spider_tree, star_tree

    g = erdos_renyi(97, 5.0, seed=7)  # ragged shard sizes on purpose
    family = [path_tree(3), star_tree(4), spider_tree([2, 1])]
    k = max(t.n for t in family)
    rng = np.random.default_rng(13)
    coloring = rng.integers(0, k, g.n).astype(np.int32)
    want = [count_colorful_maps(g, t, coloring) for t in family]

    for mode in ("alltoall", "pipeline", "adaptive", "ring"):
        for fuse in (False, True):
            c = Counter.from_graph(
                g,
                family[-1],
                backend="distributed",
                num_shards=8,
                mode=mode,
                fuse=fuse,
            )
            got = c.count_coloring_many(family, coloring)
            ok = np.allclose(got, want, rtol=1e-6)
            check(f"multi_{mode}_fuse{int(fuse)}_P8", ok, f"got {got} want {want}")

    # keyed estimate_many == per-template keyed estimates, sample for sample
    cd = Counter.from_graph(
        g, family[-1], backend="distributed", num_shards=8, mode="pipeline"
    )
    res = cd.estimate_many(family, n_iter=12, key=jax.random.key(3), batch=6)
    ok_shape = res.samples.shape == (12, 3)
    parity = True
    for i, t in enumerate(family):
        ci = Counter.from_graph(
            g,
            t,
            backend="distributed",
            num_shards=8,
            mode="pipeline",
            n_colors=res.k,
        )
        ri = ci.estimate(n_iter=12, key=jax.random.key(3), batch=6)
        parity = parity and np.allclose(ri.samples, res.samples[:, i], rtol=1e-6)
    check("multi_keyed_estimate_parity_P8", ok_shape and parity, f"shape {res.samples.shape}")


def test_compaction():
    """Active-frontier compaction over 8 real shards (DESIGN.md §15).

    The compacted exchange (per-peer [rc, B+1] slabs on alltoall/pipeline,
    compacted whole-shard relays on ring) and the compact combine must be
    bit-identical to the dense program on every mode x fuse, the keyed
    estimator must produce identical samples from the same key, and an
    absurdly small capacity_factor must fall back to the dense program
    without changing a single count.
    """
    from repro.core import frontier

    # drop the profitability floors (restored in the finally below): this
    # checks exactness of all three capacity kinds (exchange, ring relay,
    # combine) on a template small enough to afford at 8 shards, not
    # whether compaction wins
    saved_floors = (frontier.MIN_COMBINE_ELEMENTS, frontier.MIN_TABLE_WIDTH)
    frontier.MIN_COMBINE_ELEMENTS = 1
    frontier.MIN_TABLE_WIDTH = 1
    try:
        _run_compaction_checks()
    finally:
        frontier.MIN_COMBINE_ELEMENTS, frontier.MIN_TABLE_WIDTH = saved_floors


def _run_compaction_checks():
    from repro.core import relabel_random, rmat
    from repro.core.distributed import (
        build_distributed_plan,
        keyed_sample_fn,
        make_count_fn,
        shard_coloring,
    )
    from repro.core.templates import template

    # sparse skewed R-MAT under the paper's random partition: u7-2's deep
    # tables measure 0.10-0.43 active, so every capacity kind engages
    g = relabel_random(rmat(4096, 6000, skew=8, seed=0), seed=1)
    tree = template("u7-2")  # root's cut child is internal: exchange caps
    rng = np.random.default_rng(21)
    coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
    mesh = make_mesh((8,), ("data",))
    dense_plan = build_distributed_plan(g, tree, 8)
    plan = build_distributed_plan(
        g,
        tree,
        8,
        compact=True,
        density_threshold=0.5,
        capacity_factor=1.25,
    )
    spec = plan.compaction
    check(
        "compact_caps_engaged",
        bool(spec.exchange_caps) and bool(spec.shard_caps)
        and bool(spec.combine_caps),
        f"exchange={spec.exchange_caps} ring={spec.shard_caps} "
        f"combine={spec.combine_caps}",
    )
    check(
        "compact_caps_shrink",
        all(c < plan.r_pad for c in spec.exchange_caps.values())
        and all(c < plan.n_loc_pad for c in spec.shard_caps.values()),
        f"r_pad={plan.r_pad} n_loc_pad={plan.n_loc_pad}",
    )
    cols = jnp.asarray(shard_coloring(plan, coloring)[None])

    # compact == dense bit-for-bit (dense-vs-oracle parity is covered by
    # the other worker tests; u7-2 is beyond the exponential oracle)
    cases = [
        ("alltoall", False, "xla"), ("alltoall", True, "pallas"),
        ("pipeline", False, "pallas"), ("pipeline", True, "xla"),
        ("adaptive", False, "xla"), ("ring", False, "xla"),
        ("ring", True, "xla"),
    ]
    for mode, fuse, impl in cases:
        fd = make_count_fn(dense_plan, mesh, mode=mode, fuse=fuse, impl=impl)
        fc = make_count_fn(plan, mesh, mode=mode, fuse=fuse, impl=impl)
        d = np.asarray(fd(cols))
        c = np.asarray(fc(cols))
        ok = np.array_equal(d, c)
        check(
            f"compact_{mode}_fuse{int(fuse)}_{impl}_P8",
            ok,
            f"dense {d[0]} compact {c[0]}",
        )

    # keyed estimator: same key => identical samples, compact vs dense
    sd = keyed_sample_fn(dense_plan, mesh, mode="pipeline")
    sc = keyed_sample_fn(plan, mesh, mode="pipeline")
    a = sd(jax.random.key(4), 6)
    b = sc(jax.random.key(4), 6)
    check("compact_keyed_samples_P8", np.array_equal(a, b), f"{a[:2]} {b[:2]}")

    # overflow: tiny capacities must trip the flag and re-dispatch dense
    tiny = build_distributed_plan(
        g, tree, 8, compact=True, density_threshold=1.0, capacity_factor=1e-6
    )
    ft = make_count_fn(tiny, mesh, mode="pipeline")
    fd = make_count_fn(dense_plan, mesh, mode="pipeline")
    check(
        "compact_overflow_fallback_P8",
        np.array_equal(np.asarray(ft(cols)), np.asarray(fd(cols))),
        "",
    )


def test_compressed_exchange():
    """Narrow-wire exchange over 8 real shards (DESIGN.md §18).

    int16/int8 slabs (dense and compacted+bitmapped) must be bit-identical
    to the float32 wire on every mode, a forced saturation storm must
    escalate through the wider-wire ladder without changing a count, and
    the measured-adaptive router must calibrate and still count exactly.
    """
    from repro.core import frontier

    saved_floors = (frontier.MIN_COMBINE_ELEMENTS, frontier.MIN_TABLE_WIDTH)
    frontier.MIN_COMBINE_ELEMENTS = 1
    frontier.MIN_TABLE_WIDTH = 1
    try:
        _run_compressed_checks()
    finally:
        frontier.MIN_COMBINE_ELEMENTS, frontier.MIN_TABLE_WIDTH = saved_floors


def _run_compressed_checks():
    from repro.core import relabel_random, rmat
    from repro.core.distributed import (
        build_distributed_plan,
        make_count_fn,
        plan_route_report,
        shard_coloring,
    )
    from repro.core.templates import template
    from repro.testing import faults

    g = relabel_random(rmat(2048, 4000, skew=8, seed=2), seed=3)
    tree = template("u7-2")
    rng = np.random.default_rng(33)
    coloring = rng.integers(0, tree.n, g.n).astype(np.int32)
    mesh = make_mesh((8,), ("data",))
    plan_d = build_distributed_plan(g, tree, 8)
    plan_c = build_distributed_plan(
        g, tree, 8, compact=True, density_threshold=0.5, capacity_factor=1.25
    )
    cols = jnp.asarray(shard_coloring(plan_d, coloring)[None])

    # wide baseline per (mode, fuse); narrow wires must match bit for bit
    cases = [
        ("alltoall", False), ("alltoall", True),
        ("pipeline", False), ("pipeline", True),
        ("adaptive", False), ("ring", False), ("ring", True),
    ]
    for mode, fuse in cases:
        base = np.asarray(make_count_fn(plan_d, mesh, mode=mode, fuse=fuse)(cols))
        for wire in ("int16", "int8"):
            for plan, tag in ((plan_d, "dense"), (plan_c, "compact")):
                got = np.asarray(make_count_fn(
                    plan, mesh, mode=mode, fuse=fuse, wire_dtype=wire
                )(cols))
                check(
                    f"wire_{mode}_fuse{int(fuse)}_{wire}_{tag}_P8",
                    np.array_equal(base, got),
                    f"wide {base[0]} narrow {got[0]}",
                )

    # forced saturation storm: int8 escalates int16 -> (if needed) float32;
    # the ladder must converge on the wide answer and log the fired site
    base = np.asarray(make_count_fn(plan_d, mesh, mode="pipeline")(cols))
    fn8 = make_count_fn(plan_c, mesh, mode="pipeline", wire_dtype="int8")
    with faults.active(faults.inject("compression.saturate", at=(0, 1))) as fp:
        got = np.asarray(fn8(cols))
    check(
        "wire_saturation_storm_P8",
        np.array_equal(base, got) and [s for s, _ in fp.fired].count("compression.saturate") == 2,
        f"fired {fp.fired}",
    )

    # measured-adaptive routing: the calibrated router must pick real modes
    # and count exactly
    rep = plan_route_report(
        plan_c, mode="adaptive", wire_dtype="int16", adaptive="measured",
        mesh=mesh,
    )
    modes = {r["mode"] for r in rep["per_node"].values()}
    check(
        "wire_measured_router_P8",
        rep["calibrated"] and modes <= {"alltoall", "pipeline", "ring"},
        f"model {rep['model']} modes {modes}",
    )
    got = np.asarray(make_count_fn(
        plan_c, mesh, mode="adaptive", adaptive="measured", wire_dtype="int16"
    )(cols))
    check(
        "wire_measured_counts_P8",
        np.array_equal(base, got),
        f"wide {base[0]} measured {got[0]}",
    )


def test_moe_manual_vs_dense():
    """moe_block_manual (EP token-sharded / TP / pipelined) == dense oracle."""
    import dataclasses

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.models.layers import Initializer
    from repro.models.moe import moe_block, moe_block_manual, moe_init

    mesh = make_mesh((2, 4), ("data", "model"))
    base = get_arch("phi3.5-moe-42b-a6.6b").reduced()
    rng = np.random.default_rng(0)

    for moe_sharding, pipeline, gf, tname in (
        ("ep", False, 1, "ep_fused"),
        ("ep", True, 1, "ep_pipe_g1"),
        ("ep", True, 3, "ep_pipe_g3"),
        ("tp", False, 1, "tp"),
    ):
        cfg = dataclasses.replace(
            base,
            num_experts=4,
            experts_per_token=2,
            moe_sharding=moe_sharding,
            capacity_factor=64.0,
        )
        init = Initializer(jax.random.key(7))
        params = moe_init(init, cfg)
        x = jnp.asarray(rng.standard_normal((4, 8, cfg.d_model)).astype(np.float32) * 0.3)
        want, _ = jax.jit(
            lambda p_, x_: moe_block(p_, x_, cfg, dtype=jnp.float32)
        )(params, x)

        def body(p_, x_):
            out, aux = moe_block_manual(
                p_,
                x_,
                cfg,
                dp_axes=("data",),
                model_axis="model",
                fsdp_axis=None,
                pipeline=pipeline,
                group_factor=gf,
                dtype=jnp.float32,
            )
            return out

        pspecs = {
            "router": P(),
            "w_gate": P("model") if moe_sharding == "ep" else P(None, None, "model"),
            "w_up": P("model") if moe_sharding == "ep" else P(None, None, "model"),
            "w_down": P("model") if moe_sharding == "ep" else P(None, "model", None),
        }
        f = jax.jit(
            shard_map(
                body, mesh=mesh,
                in_specs=(pspecs, P("data", None, None)),
                out_specs=P("data", None, None),
                check_vma=False,
            )
        )
        got = np.asarray(f(params, x))
        ok = np.allclose(got, np.asarray(want), rtol=2e-4, atol=2e-4)
        check(f"moe_manual_{tname}", ok, f"max err {np.abs(got - np.asarray(want)).max():.2e}")


def test_elastic_restore():
    """Checkpoint saved from one mesh restores (re-sharded) onto another."""
    import tempfile

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.train import CheckpointManager

    rng = np.random.default_rng(5)
    tree = {"w": jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32)),
            "b": jnp.asarray(rng.standard_normal((8,)).astype(np.float32))}
    mesh_a = make_mesh((4,), ("data",))
    sha = {"w": NamedSharding(mesh_a, P("data", None)), "b": NamedSharding(mesh_a, P())}
    tree_a = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sha)

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, async_save=False)
        mgr.save(1, {"params": tree_a})
        mesh_b = make_mesh((8,), ("data",))
        shb = {"w": NamedSharding(mesh_b, P("data", None)), "b": NamedSharding(mesh_b, P())}
        out = mgr.restore(1, {"params": tree}, shardings={"params": shb})
        got = out["params"]
        ok = np.allclose(np.asarray(got["w"]), np.asarray(tree["w"])) and np.allclose(
            np.asarray(got["b"]), np.asarray(tree["b"])
        )
        resharded = got["w"].sharding.num_devices == 8
        check("elastic_restore", ok and resharded, f"devices={got['w'].sharding.num_devices}")


def test_robustness():
    """Kill-and-resume over 8 real shards (DESIGN.md §16).

    The resume invariant must hold when the sample stream crosses the full
    shard_map/exchange machinery: a run killed right after a mid-run
    checkpoint and resumed from the directory reproduces the uninterrupted
    run's samples and estimate bit for bit; a supervised run with a
    persistently failing batch quarantines it and keeps the healthy
    samples identical to the clean run's.
    """
    import tempfile

    from repro.api import Counter
    from repro.core import erdos_renyi
    from repro.core.estimator import estimate_counts
    from repro.core.supervisor import RetryPolicy, Supervisor
    from repro.core.templates import path_tree
    from repro.testing import faults

    g = erdos_renyi(97, 5.0, seed=7)  # ragged shard sizes on purpose
    tree = path_tree(3)
    key = jax.random.key(17)

    def counter():
        return Counter.from_graph(g, tree, backend="distributed", num_shards=8, mode="pipeline")

    base = counter().estimate(n_iter=12, key=key, batch=4)

    with tempfile.TemporaryDirectory() as d:
        with faults.active(faults.inject("estimator.kill", at=(0,))):
            try:
                counter().estimate(n_iter=12, key=key, batch=4, checkpoint=d, checkpoint_every=4)
                crashed = False
            except faults.InjectedCrash:
                crashed = True
        check("robust_kill_fired_P8", crashed)
        res = counter().estimate(n_iter=12, key=key, batch=4, resume=d)
        check(
            "robust_resume_bitexact_P8",
            res.resumed_from == 4
            and np.array_equal(res.samples, base.samples)
            and res.estimate == base.estimate
            and res.relative_sd == base.relative_sd,
            f"resumed_from={res.resumed_from} "
            f"est {res.estimate} want {base.estimate}",
        )

    # supervised 8-shard pipeline: batch 1 fails every attempt (occurrences
    # count attempts: batch 0 is 0, batch 1's three tries are 1-3)
    sup = Supervisor(counter().sample_fn, RetryPolicy(max_retries=2),
                     sleep=lambda _: None)
    with faults.active(faults.inject("sample.raise", at=(1, 2, 3))):
        est = estimate_counts(sup, 12, key, batch=4)
    healthy = np.concatenate([base.samples[:4], base.samples[8:]])
    check(
        "robust_quarantine_P8",
        len(est.quarantined) == 1
        and est.quarantined[0].call_index == 1
        and est.quarantined[0].attempts == 3
        and est.niter == 8
        and np.array_equal(est.samples, healthy),
        f"quarantined={[str(q) for q in est.quarantined]} niter={est.niter}",
    )


def test_elastic_coloring():
    """Shard-count independence of the keyed coloring stream.

    ``global_coloring`` makes the per-call coloring a function of
    ``(key, n, k)`` only: the same key must yield the same samples on a
    1-shard and an 8-shard plan (the ROADMAP elasticity contract), and both
    must equal the host-reconstructed coloring fed to the brute-force
    oracle.
    """
    from repro.core import erdos_renyi
    from repro.core.brute_force import count_colorful_maps
    from repro.core.distributed import (
        build_distributed_plan,
        global_coloring,
        keyed_sample_fn,
    )
    from repro.core.templates import path_tree

    g = erdos_renyi(97, 5.0, seed=7)  # ragged shard sizes on purpose
    tree = path_tree(3)
    key, batch = jax.random.key(23), 6

    samples = {}
    for shards in (1, 8):
        mesh = make_mesh((shards,), ("data",))
        plan = build_distributed_plan(g, tree, shards)
        samples[shards] = np.asarray(keyed_sample_fn(plan, mesh, mode="pipeline")(key, batch))
    check(
        "elastic_coloring_P1_vs_P8",
        np.allclose(samples[1], samples[8], rtol=1e-6),
        f"P1 {samples[1][:3]} P8 {samples[8][:3]}",
    )

    # host reconstruction: the same split + global_coloring draw, counted
    # by the exponential oracle
    plan = build_distributed_plan(g, tree, 8)
    want = np.array([
        count_colorful_maps(
            g, tree, np.asarray(global_coloring(kd, g.n, tree.n))
        ) * plan.scale
        for kd in jax.random.split(key, batch)
    ])
    check(
        "elastic_coloring_host_oracle",
        np.allclose(samples[8], want, rtol=1e-6),
        f"got {samples[8][:3]} want {want[:3]}",
    )


def test_service():
    """Counting service over 8 real shards: coalesced family passes must
    match solo runs (same key/batch/n_colors) sample for sample."""
    from repro.api import Counter
    from repro.core import erdos_renyi
    from repro.core.templates import path_tree
    from repro.serve import CountingService, ServiceConfig

    g = erdos_renyi(97, 5.0, seed=7)
    k, batch = 4, 4
    p4 = path_tree(4)
    svc = CountingService(
        g,
        n_colors=k,
        backend="distributed",
        plan_opts={"num_shards": 8, "mode": "pipeline"},
        config=ServiceConfig(batch=batch),
    )
    ta = svc.client("alice").submit("u3-1", n_iter=16)
    tb = svc.client("bob").submit(("u3-1", p4), n_iter=8)
    svc.run_until_idle()
    coalesced = svc.stats()["coalescing_factor"]

    key = jax.random.key(0)
    sa = Counter.from_graph(
        g, "u3-1", backend="distributed", num_shards=8, mode="pipeline",
        n_colors=k,
    ).estimate(16, key=key, batch=batch)
    sb = Counter.from_graph(
        g, "u3-1", backend="distributed", num_shards=8, mode="pipeline",
        n_colors=k,
    ).estimate_many(("u3-1", p4), 8, key=key, batch=batch)
    ra, rb = ta.result(), tb.result()
    check(
        "service_solo_scalar_P8",
        np.allclose(np.asarray(ra.samples), np.asarray(sa.samples), rtol=1e-6),
        f"svc {np.asarray(ra.samples)[:3]} solo {np.asarray(sa.samples)[:3]}",
    )
    check(
        "service_solo_family_P8",
        np.allclose(np.asarray(rb.samples), np.asarray(sb.samples), rtol=1e-6),
        f"svc {np.asarray(rb.samples)[0]} solo {np.asarray(sb.samples)[0]}",
    )
    check("service_coalesced_P8", coalesced > 1.0, f"factor {coalesced:.2f}")


def test_treewidth2():
    """Treewidth-2 bag programs over 8 real shards (DESIGN.md §19).

    Fixed-coloring oracle parity for cycle/diamond templates across the
    exchange modes (the bag_combine exchange rides the same wire; collapse
    psums the pinned-apex table), a mixed tree+cycle family through one
    shared DAG, the narrow int16 wire, fuse-bypass parity, and 1-vs-8
    shard parity on the single backend's exact counts.
    """
    from repro.api import Counter
    from repro.core import erdos_renyi
    from repro.core.brute_force import count_colorful_maps
    from repro.core.templates import template

    g = erdos_renyi(61, 6.0, seed=11)  # ragged last shard on purpose
    fam = ["cycle5", "diamond"]
    k = max(template(n).n for n in fam)
    rng = np.random.default_rng(29)
    coloring = rng.integers(0, k, g.n).astype(np.int32)
    want = [count_colorful_maps(g, template(n), coloring) for n in fam]

    for mode in ("alltoall", "pipeline", "ring", "adaptive"):
        c = Counter.from_graph(
            g,
            fam[0],
            backend="distributed",
            num_shards=8,
            mode=mode,
        )
        got = c.count_coloring_many(fam, coloring)
        check(f"tw2_{mode}_P8", np.allclose(got, want, rtol=1e-6), f"got {got} want {want}")

    # fuse is force-bypassed per bag node but must stay on for tree nodes
    mixed = ["u3-1", "cycle4", "cycle5"]
    km = max(template(n).n for n in mixed)
    colm = rng.integers(0, km, g.n).astype(np.int32)
    wantm = [count_colorful_maps(g, template(n), colm) for n in mixed]
    c = Counter.from_graph(
        g,
        mixed[-1],
        backend="distributed",
        num_shards=8,
        mode="pipeline",
        fuse=True,
    )
    gotm = c.count_coloring_many(mixed, colm)
    check("tw2_mixed_fuse_P8", np.allclose(gotm, wantm, rtol=1e-6), f"got {gotm} want {wantm}")

    # narrow wire: int16 slabs round-trip the bag exchange bit-exactly
    c16 = Counter.from_graph(
        g, fam[0], backend="distributed", num_shards=8, mode="alltoall",
        wire_dtype="int16",
    )
    got16 = c16.count_coloring_many(fam, coloring)
    check("tw2_int16_P8", np.allclose(got16, want, rtol=1e-6), f"got {got16} want {want}")

    # 1-vs-8 parity: the sharded bag strategy equals the in-core engine
    cs = Counter.from_graph(g, fam[0], backend="single")
    gots = cs.count_coloring_many(fam, coloring)
    check("tw2_single_vs_P8", np.allclose(gots, want, rtol=1e-6), f"got {gots} want {want}")


def main():
    # positional args select tests by substring (e.g. ``compressed_exchange``
    # runs only test_compressed_exchange — the CI distributed smoke step);
    # no args runs everything
    tests = [
        test_ring_collectives,
        test_grouped_exchange,
        test_distributed_counting,
        test_tiled_skew_parity,
        test_unified_api,
        test_multi_template,
        test_compaction,
        test_compressed_exchange,
        test_robustness,
        test_elastic_coloring,
        test_service,
        test_moe_manual_vs_dense,
        test_elastic_restore,
        test_treewidth2,
    ]
    wanted = sys.argv[1:]
    if wanted:
        tests = [t for t in tests if any(w in t.__name__ for w in wanted)]
        if not tests:
            print(f"no tests match {wanted}")
            sys.exit(2)
    for t in tests:
        t()
    if FAILURES:
        print(f"FAILED: {FAILURES}")
        sys.exit(1)
    print("ALL OK")


if __name__ == "__main__":
    main()
