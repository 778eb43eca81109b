"""Unified counting API: one ``Counter`` facade over every backend.

The paper's workload is a single logical operation — estimate the number of
copies of a tree template in a graph to (eps, delta) — so this module
exposes exactly one front-end for it, regardless of where the counting
runs:

>>> from repro.api import Counter
>>> counter = Counter.from_graph(g, "u5-2", backend="auto")
>>> result = counter.estimate(n_iter=500, delta=0.1, key=jax.random.key(0))
>>> result.estimate, result.relative_sd

Backends
--------
``single``
    The in-core engine (:mod:`repro.core.count_engine`): batched/fused
    per-coloring DP on one device.
``distributed``
    The shard_map engine (:mod:`repro.core.distributed`): vertex-sharded
    tables, pipelined adaptive-group exchange, colorings sampled on-device
    from the iteration key.
``auto``
    ``distributed`` when more than one device is visible, else ``single``.

Both backends are adapted to one protocol — ``sample_fn(key, batch) ->
float64 [batch]`` per-coloring copy estimates — and every aggregate
(median-of-means, RSD, progress) is computed by the shared estimator
(:mod:`repro.core.estimator`), so the two stacks cannot drift apart in what
they report.  New backends (multi-host, remote, cached) only need to
implement ``sample_fn``.

Plan construction is lazy: building a ``Counter`` is cheap; the first
counting call builds and caches the backend plan and its jitted functions.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Dict, Iterator, Mapping, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.count_engine import (
    build_counting_plan,
    build_edge_plan,
    build_multi_counting_plan,
    colorful_map_count,
    colorful_map_count_checked,
    colorful_map_count_many,
    colorful_map_count_many_checked,
    multi_sample_fn,
    plan_sample_fn,
)
from repro.core.estimator import (
    EstimatorState,
    estimate_counts,
    estimate_counts_many,
    niter_bound,
)
from repro.core.graphs import Graph
from repro.core.supervisor import RetryPolicy
from repro.core.templates import Tree, template_program, template as resolve_template
from repro.train.checkpoint import CheckpointManager

__all__ = [
    "CountRequest",
    "CountResult",
    "MultiCountResult",
    "Counter",
    "run",
    # serving layer (lazy re-exports; see module __getattr__)
    "CountingService",
    "ServiceClient",
    "ServiceConfig",
    "Ticket",
]

#: plan_opts understood by the single-device backend (``n_colors`` widens
#: the color budget past the template size — the shared-k contract of
#: family counting, see ``estimate_many``; ``compact``/``density_threshold``/
#: ``capacity_factor``/``probes`` drive active-frontier compaction, §15)
_SINGLE_OPTS = frozenset(
    {"root", "spmm_kind", "impl", "fuse", "tile_size", "block_size", "lane",
     "n_colors", "compact", "density_threshold", "capacity_factor", "probes"}
)
#: plan_opts understood by the distributed backend (``impl``/``fuse`` carry
#: the same kernel-routing semantics as the single-device engine;
#: ``bucket_tile`` is the §3.3 task size of the tiled bucket layout; the
#: compaction knobs compact the exchange slabs too; ``wire_dtype`` narrows
#: the exchange payload and ``adaptive`` selects the router's cost model,
#: §18)
_DIST_OPTS = frozenset(
    {"root", "bucket_tile", "num_shards", "mode", "group_factor", "impl",
     "fuse", "mesh", "data_axis", "iter_axis", "n_colors",
     "compact", "density_threshold", "capacity_factor", "probes",
     "wire_dtype", "adaptive"}
)
#: opts consumed by build_distributed_plan (rest go to make_count_fn)
_DIST_PLAN_OPTS = frozenset(
    {"root", "bucket_tile", "num_shards", "n_colors",
     "compact", "density_threshold", "capacity_factor", "probes"}
)


@dataclasses.dataclass(frozen=True)
class CountRequest:
    """A fully-specified counting job: what to count, where, how hard.

    ``plan_opts`` may carry options for either backend (e.g. a config row
    resolves to one request usable as single OR distributed); the facade
    selects the subset its chosen backend understands and rejects keys
    neither backend knows.
    """

    graph: Graph
    template: Union[str, Tree]
    backend: str = "auto"
    n_iter: Optional[int] = None
    eps: Optional[float] = None
    delta: float = 0.1
    batch: Optional[int] = None
    plan_opts: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    #: robustness spec (DESIGN.md §16): bounded retry of transient sample
    #: faults, checkpoint cadence (iterations; needs a checkpoint dir at run
    #: time), and optional early stop at a target relative standard error
    max_retries: Optional[int] = None
    checkpoint_every: int = 0
    target_rsd: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class CountResult:
    """Estimate plus the provenance needed to read it."""

    estimate: float  # median-of-means copy estimate (the paper's output)
    mean: float  # plain mean estimate
    relative_sd: float  # empirical RSD of per-iteration estimates
    niter: int
    samples: np.ndarray  # per-iteration copy estimates
    backend: str  # "single" | "distributed"
    template: str
    graph: str
    delta: float
    eps: Optional[float]
    elapsed_s: float
    #: batches the supervisor gave up on (QuarantinedBatch records) — their
    #: iterations are EXCLUDED from the aggregates above, never silently
    #: folded in; an empty tuple means every dispatched batch contributed
    quarantined: tuple = ()
    #: iterations restored from a checkpoint before this call ran (0 on a
    #: fresh run) — progress and RSD already account for them
    resumed_from: int = 0

    def __str__(self) -> str:
        extra = ""
        if self.resumed_from:
            extra += f", resumed at {self.resumed_from}"
        if self.quarantined:
            extra += f", {len(self.quarantined)} batch(es) quarantined"
        return (
            f"CountResult({self.template} in {self.graph or 'graph'}: "
            f"{self.estimate:.6g} via {self.backend}, "
            f"RSD {self.relative_sd:.2f}, {self.niter} colorings, "
            f"{self.elapsed_s:.2f}s{extra})"
        )


@dataclasses.dataclass(frozen=True)
class MultiCountResult:
    """One family run: per-template estimates from shared colorings.

    All array fields are indexed ``[template]`` (``samples`` is
    ``[niter, template]``); ``result[i]`` gives template ``i``'s view as a
    plain :class:`CountResult`.  ``unique_tables``/``chain_tables`` record
    the cross-template reuse the compiled DAG achieved: unique subtree
    tables computed per coloring vs. the sum of the per-template chains.
    """

    templates: tuple  # template names
    estimates: np.ndarray  # [T] median-of-means copy estimates
    means: np.ndarray  # [T]
    relative_sds: np.ndarray  # [T]
    samples: np.ndarray  # [niter, T] per-iteration copy estimates
    niter: int
    backend: str
    graph: str
    k: int  # shared color budget
    unique_tables: int  # nodes in the deduplicated DAG
    chain_tables: int  # sum of per-template chain nodes
    delta: float
    eps: Optional[float]
    elapsed_s: float
    quarantined: tuple = ()  # excluded batches (shared by all templates)
    resumed_from: int = 0  # iterations restored from checkpoint

    def __len__(self) -> int:
        return len(self.templates)

    def __getitem__(self, i: int) -> CountResult:
        return CountResult(
            estimate=float(self.estimates[i]),
            mean=float(self.means[i]),
            relative_sd=float(self.relative_sds[i]),
            niter=self.niter,
            samples=self.samples[:, i],
            backend=self.backend,
            template=self.templates[i],
            graph=self.graph,
            delta=self.delta,
            eps=self.eps,
            elapsed_s=self.elapsed_s,
            quarantined=self.quarantined,
            resumed_from=self.resumed_from,
        )

    def __iter__(self):
        return (self[i] for i in range(len(self)))

    def __str__(self) -> str:
        per = ", ".join(f"{t}={e:.6g}" for t, e in zip(self.templates, self.estimates))
        return (
            f"MultiCountResult({per} in {self.graph or 'graph'} via "
            f"{self.backend}, k={self.k}, {self.unique_tables}/"
            f"{self.chain_tables} unique tables, {self.niter} colorings, "
            f"{self.elapsed_s:.2f}s)"
        )


def _retry_policy(
    retry: Optional[RetryPolicy], max_retries: Optional[int]
) -> Optional[RetryPolicy]:
    if retry is not None:
        return retry
    if max_retries is not None:
        return RetryPolicy(max_retries=max_retries)
    return None


def _resolve_checkpointing(checkpoint, resume):
    """Normalize the (checkpoint, resume) knobs into (manager, state).

    ``checkpoint`` is a directory path or a ready
    :class:`~repro.train.checkpoint.CheckpointManager`; ``resume`` is a
    bool (use the checkpoint's latest readable state) or a directory path
    (which doubles as the checkpoint destination — the ``--resume DIR``
    CLI contract).  Managers built here write synchronously: estimator
    state is tiny, and a synchronous save is what makes "killed after the
    save at iteration N" a well-defined resume point.
    """
    if isinstance(resume, (str, os.PathLike)):
        checkpoint = checkpoint if checkpoint is not None else resume
        resume = True
    mgr = None
    if checkpoint is not None:
        mgr = checkpoint if isinstance(checkpoint, CheckpointManager) \
            else CheckpointManager(str(checkpoint), async_save=False)
    state = None
    if resume:
        if mgr is None:
            raise ValueError(
                "resume requires a checkpoint directory (checkpoint=DIR or "
                "resume=DIR) or a CheckpointManager"
            )
        latest = mgr.load_latest()
        if latest is not None:
            state = EstimatorState.from_arrays(latest[1]["estimator"])
    return mgr, state


def _resolve_backend(backend: str, plan_opts: Mapping[str, Any]) -> str:
    if backend == "auto":
        # an explicit mesh is an unambiguous request for the sharded engine;
        # otherwise shard only when this host actually has multiple devices
        multi = plan_opts.get("mesh") is not None or jax.device_count() > 1
        return "distributed" if multi else "single"
    if backend not in ("single", "distributed"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


class Counter:
    """Facade: one object that counts a template in a graph, anywhere.

    Construct with :meth:`from_graph` (or :meth:`from_request`); then

    * :meth:`estimate` — the (eps, delta) estimator (Algorithm 1);
    * :meth:`estimate_many` — a whole template family in one pass over the
      deduplicated subtree DAG (shared colorings, per-template estimates);
    * :meth:`count_one` — one coloring iteration from a key;
    * :meth:`count_coloring` — exact colorful map count for a FIXED
      coloring (backend-parity / oracle testing);
    * :meth:`count_coloring_many` — the family analogue, per-template;
    * :meth:`sample_stream` — endless stream of estimate batches for
      incremental consumption and serving;
    * :attr:`sample_fn` — the raw backend protocol, for compile warm-up
      and for composing with external aggregators.
    """

    def __init__(self, graph: Graph, tree: Tree, backend: str, plan_opts: Dict[str, Any]):
        self.graph = graph
        self.tree = tree
        self.backend = backend
        self.plan_opts = plan_opts
        self._plan = None
        self._edge_plan = None  # single backend: shared by all its plans
        self._mesh = None
        self._num_shards: Optional[int] = None
        self._fn_kw: Dict[str, Any] = {}
        self._plan_kw: Dict[str, Any] = {}
        self._sample_fn = None
        self._coloring_fn = None  # fixed-coloring counter (parity/oracle)
        self._families: Dict[tuple, Dict[str, Any]] = {}  # estimate_many state

    # ------------------------------------------------------------- builders
    @classmethod
    def from_graph(
        cls,
        graph: Graph,
        template: Union[str, Tree],
        *,
        backend: str = "auto",
        **plan_opts: Any,
    ) -> "Counter":
        """Build a counter for ``template`` (name or Tree) over ``graph``.

        ``plan_opts`` may mix options of both backends; keys the resolved
        backend does not understand are dropped (so one option set can feed
        either backend), but keys unknown to BOTH backends raise.
        """
        unknown = set(plan_opts) - (_SINGLE_OPTS | _DIST_OPTS)
        if unknown:
            raise TypeError(f"unknown plan_opts: {sorted(unknown)}")
        tree = resolve_template(template) if isinstance(template, str) else template
        resolved = _resolve_backend(backend, plan_opts)
        keep = _SINGLE_OPTS if resolved == "single" else _DIST_OPTS
        opts = {k: v for k, v in plan_opts.items() if k in keep}
        return cls(graph, tree, resolved, opts)

    @classmethod
    def from_request(cls, request: CountRequest) -> "Counter":
        return cls.from_graph(
            request.graph,
            request.template,
            backend=request.backend,
            **dict(request.plan_opts),
        )

    def with_options(self, **overrides: Any) -> "Counter":
        """A new Counter sharing this one's built plan, with different
        execution options (distributed backend only).

        Plan construction (edge tiling, request lists) is the expensive
        host-side step; ``with_options(mode=..., group_factor=..., impl=...,
        fuse=...)`` swaps only the communication schedule / kernel routing —
        e.g. comparing all four exchange modes costs one plan build, not
        four.  ``bucket_tile`` alone changes the §3.3 tiled bucket layout
        itself, so overriding it rebuilds the plan (lazily) instead of
        sharing it.
        """
        allowed = {"mode", "group_factor", "impl", "fuse", "iter_axis",
                   "bucket_tile", "wire_dtype", "adaptive"}
        if self.backend != "distributed":
            raise ValueError(
                f"with_options is for the distributed backend; this Counter "
                f"uses the {self.backend!r} backend"
            )
        bad = set(overrides) - allowed
        if bad:
            raise TypeError(
                f"with_options on the {self.backend!r} backend only swaps "
                f"{sorted(allowed)}; got {sorted(bad)}"
            )
        self._build_distributed()
        ax = overrides.get("iter_axis")
        if ax and ax not in self._mesh.axis_names:
            raise ValueError(
                f"iter_axis {ax!r} is not an axis of the mesh "
                f"{self._mesh.axis_names} — pass an explicit mesh containing "
                f"it to from_graph"
            )
        clone = Counter(self.graph, self.tree, self.backend, {**self.plan_opts, **overrides})
        if ("bucket_tile" in overrides and overrides["bucket_tile"] != self._plan.bucket_tile):
            return clone  # different tiling: plan rebuilds lazily
        clone._plan = self._plan
        clone._mesh = self._mesh
        fn_over = {k: v for k, v in overrides.items() if k != "bucket_tile"}
        clone._fn_kw = {**self._fn_kw, **fn_over}
        return clone

    # ------------------------------------------------------------- plumbing
    @property
    def k(self) -> int:
        return self.tree.n

    def _shared_edge_plan(self):
        """The graph's neighbor-sum layout, built once for every plan of
        this Counter (its template and each ``estimate_many`` family)."""
        if self._edge_plan is None:
            keys = ("spmm_kind", "tile_size", "block_size")
            opts = {k: v for k, v in self.plan_opts.items() if k in keys}
            self._edge_plan = build_edge_plan(self.graph, **opts)
        return self._edge_plan

    def _build_single(self):
        if self._plan is None:
            self._plan = build_counting_plan(
                self.graph, self.tree, spmm_plan=self._shared_edge_plan(), **self.plan_opts
            )
        return self._plan

    def _dist_ctx(self):
        """Resolve the mesh, shard count, and option split ONCE — shared by
        the single-template plan and any ``estimate_many`` family plans."""
        if self._num_shards is not None:
            return
        from repro.launch.mesh import make_mesh

        opts = dict(self.plan_opts)
        mesh = self._mesh if self._mesh is not None else opts.pop("mesh", None)
        opts.pop("mesh", None)
        num_shards = opts.pop("num_shards", None)
        self._plan_kw = {k: v for k, v in opts.items() if k in _DIST_PLAN_OPTS}
        self._fn_kw = {k: v for k, v in opts.items() if k not in _DIST_PLAN_OPTS}
        data_axis = self._fn_kw.get("data_axis", "data")
        if mesh is not None:
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            num_shards = num_shards or sizes[data_axis]
            if num_shards != sizes[data_axis]:
                raise ValueError(
                    f"num_shards={num_shards} does not match the mesh's "
                    f"{data_axis!r} axis size {sizes[data_axis]}"
                )
        else:
            # a config may ask for more shards than this host has
            num_shards = min(num_shards or jax.device_count(),
                             jax.device_count())
            mesh = make_mesh((num_shards,), (data_axis,))
        ax = self._fn_kw.get("iter_axis")
        if ax and ax not in mesh.axis_names:
            raise ValueError(
                f"iter_axis {ax!r} is not an axis of the mesh "
                f"{mesh.axis_names} — pass an explicit mesh containing it"
            )
        self._mesh = mesh
        self._num_shards = num_shards

    def _place(self, plan):
        """Lay a distributed plan's shards out on this Counter's mesh, once."""
        from repro.core.distributed import place_plan

        return place_plan(plan, self._mesh, self._fn_kw.get("data_axis", "data"))

    def _build_distributed(self):
        if self._plan is None:
            from repro.core.distributed import build_distributed_plan

            self._dist_ctx()
            self._plan = self._place(build_distributed_plan(
                self.graph, self.tree, self._num_shards, **self._plan_kw
            ))
        return self._plan

    def _iter_size(self) -> int:
        """Size of the iteration mesh axis (1 when colorings aren't sharded)."""
        ax = self._fn_kw.get("iter_axis")
        if not ax:
            return 1
        return dict(zip(self._mesh.axis_names, self._mesh.devices.shape))[ax]

    @property
    def sample_fn(self):
        """The backend protocol: ``sample_fn(key, batch) -> float64 [batch]``.

        Calling it once before timing a run warms the jit cache for that
        batch size (compile stays outside the measurement).
        """
        if self._sample_fn is None:
            if self.backend == "single":
                self._sample_fn = plan_sample_fn(self._build_single())
            else:
                from repro.core.distributed import keyed_sample_fn

                plan = self._build_distributed()
                self._sample_fn = keyed_sample_fn(plan, self._mesh, **self._fn_kw)
        return self._sample_fn

    @property
    def plan(self):
        """The lazily-built backend plan (CountingPlan or DistributedPlan)."""
        return self._build_single() if self.backend == "single" else self._build_distributed()

    @property
    def scale(self) -> float:
        """k^k / k! / |Aut| — maps colorful map counts to copy estimates."""
        return self.plan.scale

    def _signature_extra(self, *, family=None, k: Optional[int] = None) -> str:
        """Workload identity for checkpoint/resume safety.

        Deliberately does NOT include the shard count: the keyed coloring
        stream is shard-count-independent (``distributed.global_coloring``),
        so a checkpoint taken at P shards is a valid prefix of the same run
        resumed at P' — the ROADMAP elasticity contract.  A widened color
        budget (``n_colors``) DOES change the stream and is part of the
        identity.
        """
        what = f"family={','.join(family)}|k={k}" if family else self.tree.name
        extra = (f"{self.graph.name}|V={self.graph.n}|"
                 f"E={self.graph.num_edges}|{what}|{self.backend}")
        n_colors = self.plan_opts.get("n_colors")
        if not family and n_colors is not None:
            extra += f"|k={n_colors}"
        return extra

    # ------------------------------------------------------------- counting
    def estimate(
        self,
        n_iter: Optional[int] = None,
        *,
        eps: Optional[float] = None,
        delta: float = 0.1,
        key: Optional[jax.Array] = None,
        batch: Optional[int] = None,
        progress: bool = False,
        target_rsd: Optional[float] = None,
        checkpoint=None,
        checkpoint_every: int = 0,
        resume: Union[bool, str] = False,
        retry: Optional[RetryPolicy] = None,
        max_retries: Optional[int] = None,
    ) -> CountResult:
        """(eps, delta)-estimate of the copy count — Algorithm 1, any backend.

        ``n_iter`` defaults to the worst-case ``niter_bound(k, eps, delta)``
        when ``eps`` is given (beware: exponential in k); practical runs pass
        an explicit budget and read the empirical RSD, as the paper does.
        ``batch`` colorings are evaluated per backend dispatch (default 8).

        Robustness (DESIGN.md §16): ``checkpoint=DIR`` +
        ``checkpoint_every=N`` persist the estimator state every N
        iterations; ``resume=True`` (or ``resume=DIR``) continues a killed
        run from the latest readable checkpoint and returns the *same*
        result an uninterrupted run produces — progress, RSD, and the
        ``target_rsd`` early stop all start from the restored group sums,
        not from zero.  ``max_retries``/``retry`` supervise the backend:
        transient sample faults retry with backoff, corrupt payloads
        (NaN/Inf/negative) hard-fault, and persistently failing batches are
        quarantined and reported on the result.
        """
        if n_iter is None:
            if eps is None:
                raise ValueError("pass n_iter or eps (to derive the bound)")
            n_iter = niter_bound(self.k, eps, delta)
        if key is None:
            key = jax.random.key(0)
        b = batch or min(8, n_iter)
        sample = self.sample_fn  # builds the plan (and resolves shards)
        mgr, state = _resolve_checkpointing(checkpoint, resume)
        t0 = time.perf_counter()
        est = estimate_counts(
            sample,
            n_iter,
            key,
            delta=delta,
            batch=b,
            progress=progress,
            retry=_retry_policy(retry, max_retries),
            checkpoint=mgr,
            checkpoint_every=checkpoint_every,
            resume=state,
            target_rsd=target_rsd,
            signature_extra=self._signature_extra(),
        )
        elapsed = time.perf_counter() - t0
        return CountResult(
            estimate=est.estimate,
            mean=est.mean,
            relative_sd=est.relative_sd,
            niter=est.niter,
            samples=est.samples,
            backend=self.backend,
            template=self.tree.name,
            graph=self.graph.name,
            delta=delta,
            eps=eps,
            elapsed_s=elapsed,
            quarantined=est.quarantined,
            resumed_from=est.resumed_from,
        )

    def count_one(self, key: jax.Array) -> float:
        """One coloring iteration: an unbiased copy estimate from ``key``."""
        return float(self.sample_fn(key, 1)[0])

    def count_coloring(self, coloring: np.ndarray) -> float:
        """Exact colorful map count for a FIXED global coloring ``[n]``.

        This is the deterministic quantity both backends must agree on bit
        for bit (the backend-parity invariant); multiply by :attr:`scale`
        for the per-iteration copy estimate.
        """
        coloring = np.asarray(coloring, np.int32).reshape(-1)
        if coloring.shape[0] != self.graph.n:
            raise ValueError(f"coloring has {coloring.shape[0]} entries, "
                             f"graph has {self.graph.n} vertices")
        if self.backend == "single":
            plan = self._build_single()
            col = np.zeros(plan.n_pad, np.int32)
            col[: self.graph.n] = coloring
            if plan.compaction is not None and plan.compaction.enabled:
                maps, ok = colorful_map_count_checked(plan, jnp.asarray(col))
                if bool(ok):
                    return float(maps)
                # capacity overflow: recompute on the dense program
            return float(colorful_map_count(plan, jnp.asarray(col)))
        from repro.core.distributed import make_count_fn, shard_coloring

        plan = self._build_distributed()
        if self._coloring_fn is None:
            self._coloring_fn = make_count_fn(plan, self._mesh, **self._fn_kw)
        # replicate over the iteration axis (shard_map needs I divisible)
        cols = np.broadcast_to(
            shard_coloring(plan, coloring)[None],
            (self._iter_size(), plan.num_shards, plan.n_loc_pad),
        )
        return float(np.asarray(self._coloring_fn(jnp.asarray(cols)))[0])

    # ------------------------------------------------------- family counting
    def _family(self, templates) -> Dict[str, Any]:
        """Build (and cache) the shared-DAG state for a template family.

        The family is compiled once into a deduplicated
        :class:`~repro.core.templates.TemplateDag` (keyed by rooted
        canonical subtree signatures) and counted in ONE table-program pass
        per coloring on this Counter's backend — the cross-template subtree
        reuse of DESIGN.md §14.
        """
        trees = tuple(resolve_template(t) if isinstance(t, str) else t for t in templates)
        if not trees:
            raise ValueError("estimate_many needs at least one template")
        st = self._families.get(trees)
        if st is not None:
            return st
        if self.backend == "single":
            keep = {k: v for k, v in self.plan_opts.items() if k != "root"}
            plan = build_multi_counting_plan(
                self.graph, trees, spmm_plan=self._shared_edge_plan(), **keep
            )
            st = {"plan": plan, "sample_fn": multi_sample_fn(plan), "coloring_fn": None}
        else:
            from repro.core.distributed import build_distributed_plan

            self._dist_ctx()
            plan_kw = {k: v for k, v in self._plan_kw.items() if k != "root"}
            plan = self._place(
                build_distributed_plan(self.graph, trees, self._num_shards, **plan_kw)
            )
            st = {"plan": plan, "sample_fn": None, "coloring_fn": None}
        self._families[trees] = st
        return st

    def estimate_many(
        self,
        templates,
        n_iter: Optional[int] = None,
        *,
        eps: Optional[float] = None,
        delta: float = 0.1,
        key: Optional[jax.Array] = None,
        batch: Optional[int] = None,
        progress: bool = False,
        target_rsd: Optional[float] = None,
        checkpoint=None,
        checkpoint_every: int = 0,
        resume: Union[bool, str] = False,
        retry: Optional[RetryPolicy] = None,
        max_retries: Optional[int] = None,
    ) -> MultiCountResult:
        """(eps, delta)-estimates for a whole template family in one pass.

        Every coloring iteration runs the family's deduplicated DAG once:
        subtree tables shared across templates (canonically-identical
        rooted subtrees) are computed a single time and every template root
        reads its own entry — counting N related templates costs the
        unique-table work, not N chains.  All templates share one coloring
        of ``k = max template size`` colors (or ``n_colors``), and each
        gets its own unbiased scale ``k^t (k-t)!/k!/|Aut|``; per-template
        median-of-means/RSD come from the same vectorized estimator as the
        scalar path.  With the same ``key``, a per-template ``estimate`` on
        a Counter built with ``n_colors=k`` sees the identical colorings —
        the two agree sample for sample (the family-parity invariant).

        The robustness keywords (checkpoint/resume/retry/target_rsd) behave
        exactly as on :meth:`estimate`; the checkpointed state banks the
        full ``[iter, T]`` sample matrix, and ``target_rsd`` gates on the
        worst template.
        """
        st = self._family(templates)
        plan = st["plan"]
        if n_iter is None:
            if eps is None:
                raise ValueError("pass n_iter or eps (to derive the bound)")
            n_iter = niter_bound(plan.k, eps, delta)
        if key is None:
            key = jax.random.key(0)
        b = batch or min(8, n_iter)
        if st["sample_fn"] is None:  # distributed: keyed shard_map sampler
            from repro.core.distributed import keyed_sample_fn

            st["sample_fn"] = keyed_sample_fn(plan, self._mesh, **self._fn_kw)
        dag = plan.dag if self.backend == "single" else plan.program
        chain_tables = sum(len(template_program(t).nodes) for t in plan.templates)
        names = tuple(t.name or f"tree{i}" for i, t in enumerate(plan.templates))
        mgr, state = _resolve_checkpointing(checkpoint, resume)
        t0 = time.perf_counter()
        est = estimate_counts_many(
            st["sample_fn"],
            n_iter,
            key,
            delta=delta,
            batch=b,
            progress=progress,
            retry=_retry_policy(retry, max_retries),
            checkpoint=mgr,
            checkpoint_every=checkpoint_every,
            resume=state,
            target_rsd=target_rsd,
            signature_extra=self._signature_extra(family=names, k=plan.k),
        )
        elapsed = time.perf_counter() - t0
        return MultiCountResult(
            templates=names,
            estimates=est.estimates,
            means=est.means,
            relative_sds=est.relative_sds,
            samples=est.samples,
            niter=est.niter,
            backend=self.backend,
            graph=self.graph.name,
            k=plan.k,
            unique_tables=len(dag.nodes),
            chain_tables=chain_tables,
            delta=delta,
            eps=eps,
            elapsed_s=elapsed,
            quarantined=est.quarantined,
            resumed_from=est.resumed_from,
        )

    def count_coloring_many(self, templates, coloring: np.ndarray) -> np.ndarray:
        """Exact per-template colorful map counts for a FIXED coloring.

        The family analogue of :meth:`count_coloring` (the deterministic
        backend-parity quantity): one shared-DAG pass, float64
        ``[num_templates]``; multiply by the family plan's ``scales`` for
        copy estimates.  The coloring must use the family's shared color
        budget ``k``.
        """
        st = self._family(templates)
        plan = st["plan"]
        coloring = np.asarray(coloring, np.int32).reshape(-1)
        if coloring.shape[0] != self.graph.n:
            raise ValueError(f"coloring has {coloring.shape[0]} entries, "
                             f"graph has {self.graph.n} vertices")
        if self.backend == "single":
            col = np.zeros(plan.n_pad, np.int32)
            col[: self.graph.n] = coloring
            if plan.compaction is not None and plan.compaction.enabled:
                maps, ok = colorful_map_count_many_checked(plan, jnp.asarray(col))
                if bool(ok):
                    return np.asarray(maps, np.float64)
            return np.asarray(colorful_map_count_many(plan, jnp.asarray(col)), np.float64)
        from repro.core.distributed import make_count_fn, shard_coloring

        if st["coloring_fn"] is None:
            st["coloring_fn"] = make_count_fn(plan, self._mesh, **self._fn_kw)
        cols = np.broadcast_to(
            shard_coloring(plan, coloring)[None],
            (self._iter_size(), plan.num_shards, plan.n_loc_pad),
        )
        return np.asarray(st["coloring_fn"](jnp.asarray(cols)), np.float64)[0]

    def sample_stream(
        self, key: Optional[jax.Array] = None, *, batch: int = 8
    ) -> Iterator[np.ndarray]:
        """Endless stream of per-coloring estimate batches (float64 [batch]).

        For incremental/serving use: consume until the caller's own
        convergence criterion is met, feed a live dashboard, etc.  The key
        is split per step, so the stream is reproducible from ``key``.
        """
        if key is None:
            key = jax.random.key(0)
        while True:
            with obs.span("stream.next_key"):
                key, sub = jax.random.split(key)
            yield self.sample_fn(sub, batch)

    # ---------------------------------------------------------------- serving
    def serve(self, *, n_colors: Optional[int] = None, config=None,
              start: bool = False, **config_kw):
        """A resident :class:`~repro.serve.CountingService` on this graph.

        The service loads the graph once and serves a multi-tenant request
        stream: plan-cache reuse across requests, coalesced coloring
        passes, per-tenant fair scheduling (see DESIGN.md §17), and the §20
        hardening — driver thread, deadlines/cancellation, backpressure,
        supervised passes.  It runs with a fixed shared color budget —
        ``n_colors`` defaults to this Counter's own
        (``plan_opts['n_colors']`` or the template size), and every
        request's results are bit-identical to a solo
        ``Counter.estimate``/``estimate_many`` at that budget.

        ``start=True`` launches the background driver thread before
        returning; any extra keyword (``max_pending=...``,
        ``shed_oldest=True``, ``timeout_s=...``) builds the
        :class:`~repro.serve.ServiceConfig` in place of ``config``.
        """
        from repro.serve import CountingService, ServiceConfig

        if config_kw:
            if config is not None:
                raise ValueError("pass config= or ServiceConfig kwargs, not both")
            config = ServiceConfig(**config_kw)
        k = n_colors or self.plan_opts.get("n_colors") or self.k
        opts = {key: v for key, v in self.plan_opts.items() if key != "n_colors"}
        svc = CountingService(
            self.graph,
            n_colors=k,
            backend=self.backend,
            plan_opts=opts,
            config=config,
        )
        return svc.start() if start else svc


def __getattr__(name):
    # lazy serving re-exports: repro.serve imports repro.api at module
    # scope, so the reverse edge must resolve at attribute time
    if name in ("CountingService", "ServiceClient", "ServiceConfig", "Ticket",
                "QueueFullError", "UnsatisfiableRequestError"):
        import repro.serve as _serve

        return getattr(_serve, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def run(
    request: CountRequest,
    *,
    key: Optional[jax.Array] = None,
    progress: bool = False,
    checkpoint=None,
    resume: Union[bool, str] = False,
) -> CountResult:
    """One-shot: resolve a :class:`CountRequest` and run its estimate.

    The request's robustness spec (``max_retries``, ``checkpoint_every``,
    ``target_rsd``) applies; ``checkpoint``/``resume`` name where the state
    lives, since a directory is a property of the invocation, not of the
    workload.
    """
    counter = Counter.from_request(request)
    return counter.estimate(
        request.n_iter,
        eps=request.eps,
        delta=request.delta,
        key=key,
        batch=request.batch,
        progress=progress,
        max_retries=request.max_retries,
        target_rsd=request.target_rsd,
        checkpoint=checkpoint,
        checkpoint_every=request.checkpoint_every,
        resume=resume,
    )
