"""Smoke test of the subgraph counter on a TPU: the main path, end to end.

    python3 chip_smoke.py [--seed N]     # one chip
    python3 chip_smoke.py --chips 4      # the distributed exchange, 4 chips

One chip, in order (each phase fails loudly):

* device    -- the first JAX device must be a TPU.
* oracle    -- fixed colorings of u5-2, u7-2 and cycle5 on a small seeded
               RMAT, fuse off/on x edge/block layouts, through
               ``Counter.count_coloring``; every count must equal the
               brute-force oracle exactly.  Runs each compiled Pallas kernel.
* real size -- a Graph500-parameter Kronecker graph (scale 20, edge factor
               16, initiator 0.57/0.19/0.19/0.05, random relabel) counting
               u7-2 through ``Counter.estimate``, the ``launch/count.py
               --mode single`` path, at the largest coloring batch that
               fits 3/4 of HBM by ``memory_analysis()``.  One fixed
               coloring's count must match the host CPU backend's.
* service   -- ``Counter.serve`` on the same graph answers three requests
               from two tenants; each result must match a solo
               ``Counter.estimate`` / ``estimate_many`` with the same key:
               to float32 rounding there, and bit for bit on the small
               graph, where float32 holds every count exactly.

``--chips 4`` runs only the distributed path and its reference: scale 21,
u7-2 over 4 shards, every exchange mode on float32 and int16 wires, each
fixed-coloring count against the in-core count on the host CPU.

The last line of standard output is the JSON result; any failure exits
non-zero without printing it.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

ORACLE_TEMPLATES = ("u5-2", "u7-2", "cycle5")
REAL_TEMPLATE = "u7-2"
REAL_ITERS = 32
#: float32 tables round above 2^24; the chip and the host CPU sum in
#: different orders, so large counts agree to this relative tolerance
RTOL = 1e-5


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Seconds JAX spends getting compiled programs (XLA compiles and
    persistent-cache reads) and its persistent-cache hits, from
    ``jax.monitoring``.  A warm cache shows as fewer seconds."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self) -> str:
        return (f"{self.programs} programs in {self.seconds:.1f}s, "
                f"{self.hits} from the persistent cache")


def graph500(scale: int, seed: int):
    """Graph500 Kronecker graph: 2^scale vertices, edge factor 16, the
    0.57/0.19/0.19/0.05 initiator (``skew=8``), randomly relabeled."""
    from repro.core.graphs import relabel_random, rmat

    return relabel_random(rmat(2**scale, 16 * 2**scale, skew=8, seed=seed), seed=seed)


def _kernels(plan) -> str:
    from repro.core.count_engine import node_kernels

    return "; ".join(f"n{i} {c}" for i, c in sorted(node_kernels(plan).items()))


def device_phase(chips: int):
    import jax

    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX's first device is {dev.platform!r} ({dev.device_kind}); "
            f"this smoke runs only on a chip"
        )
    check(jax.device_count() >= chips, f"need {chips} chips, JAX sees {jax.device_count()}")
    say(f"[device] {dev.platform} {dev.device_kind!r} x{jax.device_count()}; "
        f"compile cache {use_compile_cache()}")
    return dev


def oracle_phase(seed: int, n: int = 256, m: int = 384):
    """Fixed colorings on a small RMAT against the brute-force oracle;
    returns the graph and the Pallas kernels that ran."""
    from repro.api import Counter
    from repro.core.brute_force import count_colorful_maps
    from repro.core.count_engine import node_kernels
    from repro.core.graphs import relabel_random, rmat
    from repro.core.templates import template

    g = relabel_random(rmat(n, m, skew=3, seed=seed), seed=seed)
    rng = np.random.default_rng(seed)
    ran = set()  # Pallas kernels that ran
    for name in ORACLE_TEMPLATES:
        t = template(name)
        coloring = rng.integers(0, t.n, g.n)
        want = count_colorful_maps(g, t, coloring)
        for fuse in (False, True):
            for kind in ("edges", "blocks"):
                c = Counter.from_graph(g, name, backend="single", fuse=fuse, spmm_kind=kind)
                got = c.count_coloring(coloring)
                say(f"[oracle] {name} fuse={fuse} {kind}: {got:.0f} (oracle {want}) "
                    f"| {_kernels(c.plan)}")
                check(got == want, f"{name} fuse={fuse} {kind}: {got} != oracle {want}")
                for choice in node_kernels(c.plan).values():
                    for op, impl in (part.split("=") for part in choice.split()):
                        if impl == "pallas":
                            ran.add({"spmm": f"spmm-{kind}"}.get(op, op))
    say(f"[oracle] V={g.n} E={g.num_edges}: {len(ORACLE_TEMPLATES) * 4} counts exact; "
        f"Pallas kernels run: {sorted(ran)}")
    return g, ran


def largest_batch(plan, key, budget: int, resident: int, cap: int):
    """The largest coloring batch (at most ``cap``) whose counter fits
    ``budget`` bytes of HBM: the ``resident`` bytes already in use (the
    plan's layout, which the program reads as arguments) plus the
    program's temporaries, outputs and code by ``memory_analysis()``.
    Found from the batch-1 and batch-2 programs' growth, then confirmed by
    compiling it.  Returns ``(batch, bytes, seconds to compile batch 1)``,
    the first compile of the run (a persistent-cache hit when warm)."""
    from repro.core.count_engine import count_fn

    def need(b):
        t0 = time.perf_counter()
        m = count_fn(plan, batch=b).lower(key).compile().memory_analysis()
        own = m.temp_size_in_bytes + m.output_size_in_bytes + m.generated_code_size_in_bytes
        return resident + own, time.perf_counter() - t0

    one, t_first = need(1)
    check(one <= budget, f"one coloring needs {one / 2**30:.2f} GiB, over {budget / 2**30:.2f}")
    per = max(need(2)[0] - one, 1)
    b = max(1, min(cap, 1 + int((budget - one) // per)))
    total, _ = need(b)
    while b > 1 and total > budget:
        b -= 1
        total, _ = need(b)
    say(f"[real] HBM: {resident / 2**30:.3f} GiB resident, batch 1 needs "
        f"{one / 2**30:.3f} GiB, +{per / 2**30:.3f} GiB per coloring; batch {b} "
        f"{total / 2**30:.3f} GiB of a {budget / 2**30:.3f} GiB budget (3/4 of HBM)")
    return b, total, t_first


def real_size_phase(seed: int, scale: int = 20, n_iter: int = REAL_ITERS):
    """Scale-20 Graph500 u7-2 through ``Counter.estimate``; returns the graph."""
    import jax

    from repro.api import Counter

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    g = graph500(scale, seed)
    t_gen = time.perf_counter() - t0
    say(f"[real] Graph500 scale {scale}: V={g.n} E={g.num_edges} "
        f"max degree {g.max_degree}; generated in {t_gen:.1f}s")
    t0 = time.perf_counter()
    counter = Counter.from_graph(g, REAL_TEMPLATE, backend="single", spmm_kind="auto")
    plan = counter.plan
    t_plan = time.perf_counter() - t0
    sp = plan.spmm_plan
    lb = sp.layout_bytes
    slab = lb.get("slab_dst", 0) + lb.get("slab_cols", 0)
    flat = lb.get("rows", 0) + lb.get("cols", 0)
    say(f"[real] plan built in {t_plan:.1f}s: kind={sp.kind} "
        f"({sp.patch_density:.2f} edges/patch), {sp.slabs_per_block} slabs/block; "
        f"slab layout {slab / 1e9:.3f} GB vs edge indices {flat / 1e9:.3f} GB")
    say(f"[real] per-node kernels: {_kernels(plan)}")

    key = jax.random.key(seed)
    stats = dev.memory_stats()
    batch, _, t_compile = largest_batch(
        plan, key, stats["bytes_limit"] * 3 // 4, stats["bytes_in_use"], n_iter
    )
    say(f"[real] batch {batch}; compile {t_compile:.1f}s (batch-1 counter)")
    t0 = time.perf_counter()
    # first call: compile (or cache hit) + one batch
    jax.block_until_ready(counter.sample_fn(key, batch))
    say(f"[real] first call {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    res = counter.estimate(n_iter, key=key, batch=batch)
    dt = time.perf_counter() - t0
    ran = -(-n_iter // batch) * batch
    check(np.isfinite(res.estimate) and res.estimate > 0, f"estimate {res.estimate}")
    check(not res.quarantined, f"quarantined batches: {res.quarantined}")
    say(f"[real] u7-2 estimate {res.estimate:.6g} (mean {res.mean:.6g}, RSD "
        f"{res.relative_sd:.3f}) from {res.niter} colorings; {ran} colorings in "
        f"{dt:.2f}s = {ran / dt:.3f} colorings/s (smoke figure, not a benchmark)")

    coloring = np.random.default_rng(seed).integers(0, plan.k, g.n)
    t0 = time.perf_counter()
    chip = counter.count_coloring(coloring)
    t_chip = time.perf_counter() - t0
    peak = dev.memory_stats().get("peak_bytes_in_use", 0)
    del counter, plan
    gc.collect()
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        host = Counter.from_graph(g, REAL_TEMPLATE, backend="single", impl="xla")
        want = host.count_coloring(coloring)
    del host
    gc.collect()
    t_cpu = time.perf_counter() - t0
    rel = abs(chip - want) / max(abs(want), 1.0)
    say(f"[real] fixed coloring: chip {chip:.9g} ({t_chip:.1f}s) vs host CPU "
        f"{want:.9g} ({t_cpu:.1f}s), rel diff {rel:.3g}")
    check(rel <= RTOL, f"chip count {chip} vs host CPU {want}: rel diff {rel:.3g} > {RTOL}")
    say(f"[real] peak_bytes_in_use {peak / 2**30:.3f} GiB")
    return g


def service_phase(g, seed: int, exact: bool, batch: int = 2, n_iter: int = 4) -> None:
    """Two tenants, three requests on the resident graph, each result
    against a solo ``Counter.estimate`` / ``estimate_many`` with its key.

    The service counts every pass as one coalesced family program, the solo
    runs as their own programs.  Where every table entry is an integer below
    2^24 (``exact``, the small graph) float32 holds it exactly and the two
    must agree bit for bit; above that they round differently, so the
    scale-20 graph's results are held to ``RTOL``."""
    import jax

    from repro.api import Counter

    tag = "[service exact]" if exact else "[service]"
    key = jax.random.key(seed)
    t0 = time.perf_counter()
    svc = Counter.from_graph(g, REAL_TEMPLATE, backend="single").serve(
        start=True, batch=batch, seed=seed
    )
    try:
        alice, bob = svc.client("alice"), svc.client("bob")
        tickets = [
            alice.submit("u3-1", n_iter=n_iter, key=key),
            bob.submit(("u5-2", "u7-2"), n_iter=n_iter, key=key),
            alice.submit("u3-1", n_iter=n_iter, key=key),
        ]
        for t in tickets:
            t.wait(timeout=900)
        stats = svc.stats()
    finally:
        svc.stop()
    t_svc = time.perf_counter() - t0
    for t in tickets:
        check(t.status == "done", f"ticket {t.id} ({t.tenant}) is {t.status}: {t.error}")
    check(stats["driver"]["errors"] == 0, f"driver errors: {svc.driver_errors}")
    quarantined = stats.get("quarantined", 0)
    check(quarantined == 0, f"{quarantined} quarantined batches")
    results = [t.result() for t in tickets]
    del svc
    gc.collect()
    say(f"{tag} V={g.n}: 3 requests from 2 tenants done in {t_svc:.1f}s: "
        f"{stats.get('pass_calls', 0)} pass calls, coalescing "
        f"{stats['coalescing_factor']:.2f}, plan cache {stats['cache']}")

    solo = Counter.from_graph(g, "u3-1", backend="single", n_colors=7)
    alone = [
        solo.estimate(n_iter, key=key, batch=batch),
        solo.estimate_many(("u5-2", "u7-2"), n_iter, key=key, batch=batch),
    ]
    alone.append(alone[0])
    for t, got, want in zip(tickets, results, alone):
        a, b = np.asarray(got.samples), np.asarray(want.samples)
        check(a.shape == b.shape, f"ticket {t.id}: samples {a.shape} vs solo {b.shape}")
        rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1)))
        what = f"{tag} {t.tenant} {','.join(t.templates)}: {got}"
        if exact:
            check(np.array_equal(a, b), f"ticket {t.id} {t.templates}: samples differ "
                  f"from the solo run (max rel {rel:.3g})")
            say(f"{what} == solo, bit for bit")
        else:
            check(rel <= RTOL, f"ticket {t.id} {t.templates}: max rel diff {rel:.3g} "
                  f"from the solo run > {RTOL}")
            say(f"{what}; max rel diff from solo {rel:.3g}")
    del solo
    gc.collect()


def four_chip_phase(seed: int, scale: int = 21) -> None:
    """Scale-21 u7-2 over 4 shards: every exchange mode and wire against
    the in-core count of the same coloring on the host CPU."""
    import jax
    from jax.sharding import PartitionSpec

    from repro.api import Counter

    t0 = time.perf_counter()
    g = graph500(scale, seed)
    say(f"[4chip] Graph500 scale {scale}: V={g.n} E={g.num_edges}; "
        f"generated in {time.perf_counter() - t0:.1f}s")
    coloring = np.random.default_rng(seed).integers(0, 7, g.n)
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        host = Counter.from_graph(g, REAL_TEMPLATE, backend="single", impl="xla")
        want = host.count_coloring(coloring)
    del host
    gc.collect()
    say(f"[4chip] in-core count on the host CPU: {want:.9g} ({time.perf_counter() - t0:.1f}s)")

    t0 = time.perf_counter()
    base = Counter.from_graph(g, REAL_TEMPLATE, backend="distributed", num_shards=4)
    plan = base.plan
    say(f"[4chip] distributed plan built and placed in {time.perf_counter() - t0:.1f}s: "
        f"{plan.num_shards} shards of {plan.shard_size} vertices, "
        f"{plan.num_tiles} tiles/shard, r_pad {plan.r_pad}")
    for name, arr in zip(("tile_dst", "tile_src_local", "tile_src_compact", "tile_off",
                          "send_idx", "a2a_slab_dst", "a2a_slab_cols"), plan.device_arrays):
        check(arr.sharding.spec == PartitionSpec("data"), f"{name} sharded {arr.sharding}")
        # shard p (one [1, ...] slice) on the p-th device of the mesh
        order = [d.id for d in arr.sharding.mesh.devices.flat]
        placed = sorted((s.index[0].start, s.device.id, s.data.shape[0])
                        for s in arr.addressable_shards)
        check(placed == [(p, order[p], 1) for p in range(4)], f"{name} shards {placed}")
    devs = jax.devices()
    per_dev = [d.memory_stats()["bytes_in_use"] / 2**30 for d in devs]
    say(f"[4chip] plan arrays sharded P('data'), shard p on mesh device p (device ids "
        f"{order}); bytes in use per chip (GiB): "
        f"{', '.join(f'{d.id}:{b:.3f}' for d, b in zip(devs, per_dev))}")
    check(min(per_dev) > 0.5 * max(per_dev), "plan memory is not spread over the chips")

    for wire in ("float32", "int16"):
        for mode in ("alltoall", "pipeline", "ring", "adaptive"):
            c = base.with_options(mode=mode, wire_dtype=wire)
            t0 = time.perf_counter()
            got = c.count_coloring(coloring)
            rel = abs(got - want) / max(abs(want), 1.0)
            say(f"[4chip] {mode:<8} {wire:<7}: {got:.9g} rel diff {rel:.3g} "
                f"({time.perf_counter() - t0:.1f}s incl. compile)")
            check(rel <= RTOL, f"{mode}/{wire}: {got} vs in-core {want}")
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0) / 2**30 for d in devs]
    say(f"[4chip] peak bytes per chip (GiB): {', '.join(f'{p:.3f}' for p in peaks)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the distributed path and its reference")
    args = ap.parse_args()
    compiles = CompileLog()
    try:
        dev = device_phase(args.chips)
        if args.chips == 4:
            four_chip_phase(args.seed)
        else:
            small, ran = oracle_phase(args.seed)
            want = {"spmm-edges", "spmm-blocks", "fused", "combine"}
            check(ran == want, f"Pallas kernels run {sorted(ran)}, expected {sorted(want)}")
            say(f"[compile] after oracle: {compiles.report()}")
            g = real_size_phase(args.seed)
            say(f"[compile] after real size: {compiles.report()}")
            service_phase(g, args.seed, exact=False)
            service_phase(small, args.seed, exact=True)
    except SmokeFailure as e:
        say(f"[compile] {compiles.report()}")
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    import jax

    say(f"[compile] {compiles.report()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
