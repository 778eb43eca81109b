"""One run of one cell of BENCHMARK.json, on the chips of this machine.

    python3 chipbench/run.py --workload g500s19-u12-2 --seed 12345 --seconds 30 --trace 0

In order: the device check (the first device must be a TPU and there must be
as many as the cell asks for, or the run exits 2 and prints no result), the
configuration's graph, the program's ``Counter`` on the configuration's
backend (``single``: one chip; ``distributed``: the graph sharded over the
cell's chips) and its plan, the largest coloring batch that fits 3/4 of each
chip's HBM, a warm-up of that batch, the measured window over
``Counter.sample_stream``, and the check of the answers the window produced
against the plain reference (``reference.py``), on one chip once the
program's state is gone from all of them.  ``--trace 1`` traces the window
with the JAX profiler and reports the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared beside its limit.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``, its
configuration's file, ``traffic/<mix>.json``, ``limits/<cell>.json``, the
graph generator ``<generator>.py`` and ``metrics/<metric>.py``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
TRACE_DIR = os.path.join(BENCH_DIR, ".traces")
HBM_SHARE = 3 / 4
NO_CHIP = 2


class NoChip(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ the cell
def load_cell(name: str, root: str = ROOT):
    """The cell's entry, configuration, traffic mix, limits and metrics."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}

    def read(path):
        with open(os.path.join(root, path)) as f:
            return json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "cell": cell,
        "config": read(configs[cell["config"]]["file"]),
        "traffic": read(os.path.join("chipbench", "traffic", cell["traffic"] + ".json")),
        "limits": read(os.path.join("chipbench", "limits", name + ".json")),
        "end_to_end": mine(manifest["end_to_end"]),
        "per_layer": mine(manifest["per_layer"]),
    }


def seed_key(seed: int, stream: int):
    """A JAX key for one of the run's streams, from all the bits of ``seed``
    (``jax.random.key`` keeps only the low 32)."""
    import jax

    bits = np.random.SeedSequence([seed, stream]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(bits)


GRAPH, WARM, WINDOW, SAMPLE = range(4)


# -------------------------------------------------------------- measurement
class CompileLog:
    """Backend compiles and persistent-cache hits, from ``jax.monitoring``."""

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


def span(name: str, tracing: bool):
    if not tracing:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("chipbench." + name)


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"the first JAX device is {devs[0].platform!r} "
                     f"({devs[0].device_kind}); this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def device_arrays(obj, depth: int = 3):
    """The JAX arrays a plan holds, to wait on its placement."""
    import jax

    if isinstance(obj, jax.Array):
        return [obj]
    if depth == 0:
        return []
    if dataclasses.is_dataclass(obj):
        obj = [getattr(obj, f.name) for f in dataclasses.fields(obj)]
    elif isinstance(obj, dict):
        obj = list(obj.values())
    elif not isinstance(obj, (list, tuple)):
        return []
    return [a for x in obj for a in device_arrays(x, depth - 1)]


def program_bytes(counter, key, batch: int) -> int:
    """Bytes the compiled batch program needs beside its arguments, by
    ``memory_analysis()`` (temporaries, outputs and code); for a sharded
    program, those of one device."""
    if counter.backend == "single":
        from repro.core.count_engine import count_fn

        compiled = count_fn(counter.plan, batch=batch).lower(key).compile()
    else:
        import jax

        program, arrays = distributed_program(counter)
        keys = jax.random.key_data(jax.random.split(key, batch)).astype(np.uint32)
        compiled = program.lower(keys, *arrays).compile()
    m = compiled.memory_analysis()
    return m.temp_size_in_bytes + m.output_size_in_bytes + m.generated_code_size_in_bytes


def distributed_program(counter):
    """The jitted program behind a distributed ``Counter.sample_fn`` and the
    placed plan arrays it is called with: ``keyed_sample_fn``'s count
    function ``f`` calls ``run``, which calls ``shard_args(keys, *arrays)``.
    Lowering ``f`` itself would embed the plan arrays in the program as
    constants, so the harness lowers ``shard_args`` with the arrays as
    arguments, as ``sample_fn`` calls it."""
    import inspect

    try:
        f = inspect.getclosurevars(counter.sample_fn).nonlocals["f"]
        inner = inspect.getclosurevars(inspect.getclosurevars(f).nonlocals["run"]).nonlocals
        return inner["shard_args"], inner["arrays"]
    except KeyError as missing:
        raise SystemExit(
            f"the distributed Counter's sample_fn no longer has the closures the harness "
            f"lowers (sample_fn -> f -> run -> shard_args, arrays): {missing} not found") from None


def largest_batch(counter, key, budget: int, resident: int, cap: int):
    """The largest batch (at most ``cap``) whose program fits ``budget``
    bytes beside the ``resident`` bytes in use, from the batch-1 and
    batch-2 programs' growth, confirmed by compiling it.  Returns
    ``(batch, bytes needed, bytes per further coloring)``."""
    one = resident + program_bytes(counter, key, 1)
    if one > budget:
        raise RuntimeError(f"one coloring needs {one / 2**30:.3f} GiB, over the "
                           f"{budget / 2**30:.3f} GiB budget")
    per = max(resident + program_bytes(counter, key, 2) - one, 1)
    b = max(1, min(cap, 1 + int((budget - one) // per)))
    total = resident + program_bytes(counter, key, b)
    while b > 1 and total > budget:
        b -= 1
        total = resident + program_bytes(counter, key, b)
    return b, total, per


def stream_keys(key, steps: int):
    """The per-batch keys ``Counter.sample_stream(key)`` draws, in order."""
    import jax

    out = []
    for _ in range(steps):
        key, sub = jax.random.split(key)
        out.append(sub)
    return out


def coloring_of(backend: str, key, batch: int, slot: int, n: int, n_pad: int, k: int):
    """The coloring of one slot of a batch, drawn from the batch's key by the
    rule of the program's documented coloring stream for its backend."""
    import jax
    import jax.numpy as jnp

    if backend == "single":
        return jax.random.randint(key, (batch, n_pad), 0, k, dtype=jnp.int32)[slot, :n]
    if backend == "distributed":
        # one key per slot (the cell's mesh has no iteration axis to round
        # the batch up to); a slot's coloring depends on (key, n, k) alone
        return jax.random.randint(jax.random.split(key, batch)[slot], (n,), 0, k,
                                  dtype=jnp.int32)
    raise ValueError(f"no coloring rule for the {backend!r} backend")


# ------------------------------------------------------------------- a run
def build(spec, key, *, require_tpu: bool = True):
    """Set-up up to the warm-up: the devices, the graph, the program's
    Counter and placed plan, and the batch; ``key`` sizes the batch."""
    import jax

    from repro.api import Counter
    from repro.core.graphs import from_edges

    config, traffic = spec["config"], spec["traffic"]
    chips = spec["cell"]["chips"]
    devs = check_devices(chips) if require_tpu else jax.devices()[:chips]
    dev = devs[0]
    say(f"[device] {dev.platform} {dev.device_kind!r} x{len(devs)}")

    # graph: the configuration's generator, from its own fixed seed
    t0 = time.perf_counter()
    gspec = dict(config["graph"])
    generator = importlib.import_module(gspec.pop("generator"))
    graph_seed = gspec.pop("seed")
    n, edges = generator.edges(seed_key(graph_seed, GRAPH), **gspec)
    t_gen = time.perf_counter() - t0
    say(f"[graph] {config['name']}: V={n} E={len(edges)} generated in {t_gen:.3f}s")

    # plan: from_edges, the Counter and its placed plan
    t0 = time.perf_counter()
    g = from_edges(n, edges, config["name"])
    counter = Counter.from_graph(g, traffic["template"], backend=config["backend"],
                                 **config.get("plan_opts", {}))
    plan = counter.plan
    jax.block_until_ready(device_arrays(plan))
    t_plan = time.perf_counter() - t0
    # the single backend's coloring stream draws n_pad colors a slot; the
    # distributed one draws n, whatever the shards' padding
    n_pad = getattr(plan, "n_pad", None)
    if n_pad is not None:
        say(f"[plan] built and placed in {t_plan:.3f}s; n_pad {n_pad}")
    else:
        say(f"[plan] built and placed in {t_plan:.3f}s; {plan.num_shards} shards of "
            f"{plan.shard_size} vertices, n_loc_pad {plan.n_loc_pad}")

    # batch: the largest whose program fits 3/4 of each chip's HBM, beside
    # what the fullest chip holds
    t0 = time.perf_counter()
    stats = [d.memory_stats() or {} for d in devs]
    if all("bytes_limit" in st for st in stats):
        limit = min(st["bytes_limit"] for st in stats)
        batch, need, per = largest_batch(counter, key, int(limit * HBM_SHARE),
                                         max(st["bytes_in_use"] for st in stats),
                                         traffic["batch_cap"])
        say(f"[batch] {batch}: {need / 2**30:.3f} GiB of {limit / 2**30:.3f}"
            f" GiB; {per / 2**30:.3f} GiB per further coloring")
    else:  # a device that reports no memory: the tests' CPU runs
        batch, need, per = traffic["batch_cap"], 0, None
    t_batch = time.perf_counter() - t0
    return types.SimpleNamespace(
        devs=devs, n=n, edges=edges, counter=counter, n_pad=n_pad, batch=batch,
        per_coloring_bytes=per, need_bytes=need, backend=config["backend"],
        setup={"generate_s": t_gen, "plan_build_s": t_plan, "batch_s": t_batch},
    )


def free_program(cell) -> None:
    """Drop the program's state, so that the reference has the chip."""
    import jax

    cell.counter = None
    gc.collect()
    jax.clear_caches()


def reference_answers(spec, cell, window_key, answers_shape, picks, precision="highest"):
    """The reference's estimate of each picked answer (a flat index into
    the window's ``[batches, batch]`` answers)."""
    import reference

    traffic = spec["traffic"]
    tmpl, k = traffic["template_edges"], traffic["template_size"]
    scale = reference.copy_scale(k, reference.automorphisms(tmpl, k))
    keys = stream_keys(window_key, answers_shape[0])
    ref = reference.Reference(cell.n, cell.edges, tmpl, k, precision)
    out = []
    for p in picks:
        b, slot = divmod(int(p), cell.batch)
        col = coloring_of(cell.backend, keys[b], cell.batch, slot, cell.n, cell.n_pad, k)
        t0 = time.perf_counter()
        out.append(ref.count(col) * scale)
        say(f"[reference] {precision} batch {b} slot {slot}: {out[-1]:.9g} in "
            f"{time.perf_counter() - t0:.3f}s")
    return out


def pick_answers(seed: int, count: int, size: int):
    """The answers the check compares, drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, SAMPLE]))
    return sorted(int(p) for p in rng.choice(size, size=min(count, size), replace=False))


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want) if want else abs(got)


def run_cell(spec, seed: int, seconds: float, trace: bool, *, require_tpu: bool = True,
             trace_dir: str = "", t_start: float = T_START):
    """One run of one cell; returns the result object (the last line)."""
    import jax

    import work

    traffic, limits = spec["traffic"], spec["limits"]
    compiles = CompileLog()
    key = seed_key(seed, WARM)
    cell = build(spec, key, require_tpu=require_tpu)
    counter, batch, dev = cell.counter, cell.batch, cell.devs[0]

    # warm-up: the window's own batch shape and key split
    t0 = time.perf_counter()
    est = counter.sample_fn(key, batch)
    _, _ = jax.random.split(key)  # the stream's split, unpacked as it unpacks it
    t_warm = time.perf_counter() - t0
    say(f"[warm] batch {batch} in {t_warm:.3f}s ({np.asarray(est).shape})")

    # the measured window
    compiled_before, compile_s = compiles.programs, compiles.seconds
    setup_s = time.perf_counter() - t_start
    log_dir = ""
    if trace:
        log_dir = trace_dir or os.path.join(TRACE_DIR, f"{spec['cell']['name']}-{seed}")
        shutil.rmtree(log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
    window_key = seed_key(seed, WINDOW)
    stream = counter.sample_stream(window_key, batch=batch)
    answers, total = [], 0.0
    with span("window", trace):
        t_open = time.perf_counter()
        while True:
            with span("stream_next", trace):
                est = next(stream)
            with span("aggregate", trace):
                answers.append(est)
                total += float(np.sum(est))
            if time.perf_counter() - t_open >= seconds:
                break
        t_close = time.perf_counter()
    if trace:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"[trace] stopped and written in {time.perf_counter() - t0:.3f}s")
        # the host reads the trace while the reference keeps the chip busy
        reader = concurrent.futures.ThreadPoolExecutor(1)
        reading = reader.submit(read_trace, log_dir)
    in_window = compiles.programs - compiled_before
    done = batch * len(answers)
    rate = done / (t_close - t_open)
    say(f"[window] {len(answers)} batches of {batch} = {done} colorings in "
        f"{t_close - t_open:.3f}s: {rate:.6g} colorings/s; running estimate "
        f"{total / done:.6g}; {in_window} programs compiled in the window")
    say(f"[setup] {setup_s:.3f}s: generate {cell.setup['generate_s']:.3f}, plan "
        f"{cell.setup['plan_build_s']:.3f}, batch {cell.setup['batch_s']:.3f}, warm "
        f"{t_warm:.3f}; compile {compile_s:.3f}s over {compiled_before} programs, "
        f"{compiles.hits} persistent-cache hits")
    # the allocator's peak leaves out the program's temporaries on the TPU,
    # which memory_analysis() counts
    in_use = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in cell.devs)
    peak = max(in_use, cell.need_bytes)
    say(f"[memory] peak_bytes_in_use {in_use / 2**30:.3f} GiB; resident plus the batch "
        f"program by memory_analysis() {cell.need_bytes / 2**30:.3f} GiB")

    # the check, once the program's state is gone
    del counter, stream
    free_program(cell)
    answers = np.asarray(answers, np.float64)  # [batches, batch]
    bad_answers = int(np.sum(~np.isfinite(answers) | (answers <= 0)))
    picks = pick_answers(seed, traffic["check_colorings"], answers.size)
    t0 = time.perf_counter()
    wants = reference_answers(spec, cell, window_key, answers.shape, picks)
    gaps = []
    for p, want in zip(picks, wants):
        got = answers.flat[p]
        gaps.append(rel_gap(got, want))
        say(f"[check] batch {p // batch} slot {p % batch}: program {got:.9g} reference "
            f"{want:.9g} rel gap {gaps[-1]:.3g}")
    say(f"[check] {len(gaps)} answers against the reference in "
        f"{time.perf_counter() - t0:.3f}s")
    limit = limits["rel_gap"]
    gap = max(gaps) if gaps else float("inf")
    wrong = sum(not g_ <= limit for g_ in gaps)

    result = {
        "correct": bad_answers == 0 and wrong == 0 and bool(gaps),
        "attempted": done,
        "failed": bad_answers + wrong,
        "metrics": {},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(cell.devs), "memory_peak_bytes": int(peak)},
    }
    if trace:
        red = reading.result()
        reader.shutdown()
        if not trace_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
        busy = sum(red["busy_s"]) / len(red["busy_s"])
        result["device"].update(busy_s=busy, window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        tmpl, k = traffic["template_edges"], traffic["template_size"]
        context = {
            "setup": dict(cell.setup, compile_s=compile_s, warm_s=t_warm),
            "memory": {"per_coloring_bytes": cell.per_coloring_bytes},
            "colorings": done,
            "chips": len(cell.devs),
            "work": work.coloring_work(tmpl, k, cell.n, 2 * len(cell.edges)),
            "peak": peaks(dev.device_kind) if require_tpu else None,
            "trace": red,
        }
        t0 = time.perf_counter()
        for m in spec["per_layer"]:
            value = importlib.import_module("metrics." + m["name"]).read(context)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        say(f"[trace] per-layer metrics read in {time.perf_counter() - t0:.3f}s")
    else:
        measured = {"colorings_per_s": rate, "setup_s": setup_s}
        for m in spec["end_to_end"]:
            result["metrics"][m["name"]] = {"value": measured[m["name"]], "unit": m["unit"]}
    result["checks"] = {
        "rel_gap": {"value": gap, "limit": limit},
        "bad_answers": {"value": bad_answers, "limit": 0},
    }
    return result


def read_trace(log_dir: str):
    """The window's trace, reduced by ``trace_reduce``."""
    import trace_reduce

    t0 = time.perf_counter()
    path = trace_reduce.find_trace(log_dir)
    red = trace_reduce.reduce_trace(trace_reduce.load(path))
    say(f"[trace] {os.path.getsize(path) / 2**20:.1f} MiB read and reduced in "
        f"{time.perf_counter() - t0:.3f}s")
    return red


def peaks(kind: str):
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; have {sorted(table)}")
    return table[kind]


def use_compile_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default="", help="keep the trace here")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH_DIR]
    spec = load_cell(args.workload)
    use_compile_cache()
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          trace_dir=args.trace_dir)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return NO_CHIP
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
