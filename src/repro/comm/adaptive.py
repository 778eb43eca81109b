"""Adaptive mode selection — the paper's §3.2.2 complexity model.

The paper switches between the monolithic all-to-all and the pipelined
grouped exchange based on the sub-template's computation intensity: the
pipeline wins when per-chunk compute can hide per-chunk transfer
(overlap ratio rho_w -> 1, Eq. 14) and the extra per-step latency
``alpha * W`` is amortized; the fused collective wins for small payloads
that cannot exploit overlap but do exploit full link bandwidth.

The decision is made at trace time (per sub-template / per layer), which is
the same granularity as the paper's runtime router — under SPMD the
schedule must be static anyway (DESIGN.md §10).

Costs follow the Hockney model (Eq. 8):
    T_fused    = alpha + beta * B_total + T_comp_total
    T_pipeline = W * alpha + beta * B_chunk            (cold start, Eq. 15)
                 + sum_w max(T_comp_chunk, beta * B_chunk)
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, Optional, Tuple

__all__ = [
    "HockneyModel",
    "V5E_ICI",
    "V5E_DCI",
    "ASSUMED_LINKS",
    "assumed_model",
    "overlap_ratio",
    "pipeline_cost",
    "fused_cost",
    "choose_mode",
    "choose_mode_full",
    "calibrate",
]


@dataclasses.dataclass(frozen=True)
class HockneyModel:
    """alpha/beta link model + compute rate for one mesh axis."""

    alpha: float  # per-operation latency, seconds
    beta: float  # seconds per byte (1 / link bandwidth)
    flops_per_s: float  # effective compute rate of one device


# TPU v5e constants used throughout the roofline analysis: 197 TFLOP/s bf16,
# ~50 GB/s per ICI link; inter-pod DCI assumed 2x slower.  alpha from typical
# ICI collective latencies (~5 us per hop).
V5E_ICI = HockneyModel(alpha=5e-6, beta=1.0 / 50e9, flops_per_s=197e12)
V5E_DCI = HockneyModel(alpha=20e-6, beta=1.0 / 25e9, flops_per_s=197e12)

#: the router's assumed link model, by ``device_kind`` (``adaptive="model"``)
ASSUMED_LINKS: Dict[str, HockneyModel] = {
    "TPU v5 lite": V5E_ICI,  # the device_kind JAX reports for a v5e
    # test-only assumption: host virtual devices have no link to model;
    # CPU runs route as a v5e mesh would, so their choices match the chip's
    "cpu": V5E_ICI,
}


def assumed_model(device_kind: str) -> HockneyModel:
    """The assumed link model of ``device_kind``; an unknown kind raises
    rather than borrowing another chip's constants."""
    try:
        return ASSUMED_LINKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no assumed link model for device kind {device_kind!r} "
            f"(known: {sorted(ASSUMED_LINKS)}); route with adaptive='measured'"
        ) from None


def overlap_ratio(comp_chunk_s: float, comm_chunk_s: float) -> float:
    """rho_w of Eq. 14: fraction of a chunk transfer hidden by compute."""
    if comm_chunk_s <= 0:
        return 1.0
    return min(comp_chunk_s, comm_chunk_s) / comm_chunk_s


def pipeline_cost(
    total_bytes: float,
    total_flops: float,
    P: int,
    model: HockneyModel,
    group_factor: int = 1,
) -> float:
    """Estimated wall time of the grouped pipelined exchange (Eq. 13/15)."""
    W = max(1, math.ceil((P - 1) / max(1, group_factor)))
    b_chunk = total_bytes / max(1, P - 1) * group_factor
    comp_chunk = total_flops / max(1, P) / model.flops_per_s
    comm_chunk = model.alpha + model.beta * b_chunk
    # cold start pays one full transfer; subsequent steps overlap
    return comm_chunk + sum(
        max(comp_chunk, comm_chunk) for _ in range(W - 1)
    ) + comp_chunk


def fused_cost(total_bytes: float, total_flops: float, model: HockneyModel) -> float:
    """Estimated wall time of all-to-all + full compute (no overlap)."""
    return model.alpha + model.beta * total_bytes + total_flops / model.flops_per_s


def choose_mode(
    total_bytes: float,
    total_flops: float,
    P: int,
    model: HockneyModel = V5E_ICI,
    group_factor: int = 1,
) -> Tuple[str, dict]:
    """Pick 'pipeline' or 'alltoall' for one exchange; returns diagnostics.

    ``total_bytes``: payload this device exchanges across the axis;
    ``total_flops``: compute consuming that payload on this device.
    """
    tp = pipeline_cost(total_bytes, total_flops, P, model, group_factor)
    tf = fused_cost(total_bytes, total_flops, model)
    comp_chunk = total_flops / max(1, P) / model.flops_per_s
    comm_chunk = model.alpha + model.beta * total_bytes / max(1, P - 1)
    diag = {
        "pipeline_cost_s": tp,
        "fused_cost_s": tf,
        "rho": overlap_ratio(comp_chunk, comm_chunk),
        "intensity_flops_per_byte": total_flops / max(total_bytes, 1.0),
    }
    return ("pipeline" if tp <= tf else "alltoall"), diag


def choose_mode_full(
    a2a_bytes: float,
    ring_bytes: float,
    total_flops: float,
    P: int,
    model: HockneyModel = V5E_ICI,
    group_factor: int = 1,
) -> Tuple[str, dict]:
    """Pick among all three exchange schedules for one tree node.

    ``a2a_bytes`` is what the alltoall/pipeline schedules ship (per-peer
    request slabs, compacted+compressed); ``ring_bytes`` is the ring
    relay's whole-table volume — usually larger, but the ring's O(1)-HLO
    shift overlaps every step, so it wins when compute dominates.  The
    ring is costed as a fully pipelined (group 1) schedule over its own
    byte count.
    """
    costs: Dict[str, float] = {
        "alltoall": fused_cost(a2a_bytes, total_flops, model),
        "pipeline": pipeline_cost(a2a_bytes, total_flops, P, model, group_factor),
        "ring": pipeline_cost(ring_bytes, total_flops, P, model, 1),
    }
    mode = min(costs, key=costs.get)
    comp_chunk = total_flops / max(1, P) / model.flops_per_s
    comm_chunk = model.alpha + model.beta * a2a_bytes / max(1, P - 1)
    diag = {
        "costs_s": costs,
        "predicted_s": costs[mode],
        "rho": overlap_ratio(comp_chunk, comm_chunk),
        "intensity_flops_per_byte": total_flops / max(a2a_bytes, 1.0),
    }
    return mode, diag


# one-shot probe results, keyed by (platform, device kind, axis size):
# calibration is a property of the link, not of the plan being built
_CALIBRATION_CACHE: Dict[tuple, HockneyModel] = {}


def _time_call(fn, *args, repeats: int = 3) -> float:
    """Min-of-N wall time of a jitted call (after one warmup)."""
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    best = math.inf
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def calibrate(
    mesh,
    data_axis: str = "data",
    *,
    payload_bytes: Tuple[int, ...] = (1 << 16, 1 << 19, 1 << 22),
    repeats: int = 3,
    base: Optional[HockneyModel] = None,
) -> HockneyModel:
    """Fit alpha/beta (and a matmul flop rate) from a measured probe.

    Times one ring-shift ``ppermute`` across ``data_axis`` at each payload
    size, least-squares fits ``t = alpha + beta * bytes``, and times a
    single [n, n] matmul for ``flops_per_s``.  Runs once per
    ``(platform, device kind, P)`` — results are cached for the process.
    On a single-device axis the assumed ``base`` model is returned
    unchanged (there is no link to measure); ``base`` defaults to the
    mesh device's :func:`assumed_model`.
    """
    import jax
    import jax.numpy as jnp

    dev = mesh.devices.flat[0]
    if base is None:
        base = assumed_model(dev.device_kind)
    P = int(mesh.shape[data_axis])
    if P <= 1:
        return base
    cache_key = (dev.platform, getattr(dev, "device_kind", ""), P, payload_bytes)
    hit = _CALIBRATION_CACHE.get(cache_key)
    if hit is not None:
        return hit

    from jax.sharding import PartitionSpec as PS

    perm = [(i, (i + 1) % P) for i in range(P)]
    times = []
    for nbytes in payload_bytes:
        n = max(1, nbytes // 4)

        def shift(x):
            return jax.lax.ppermute(x, data_axis, perm)

        fn = jax.jit(
            jax.shard_map(
                shift,
                mesh=mesh,
                in_specs=PS(data_axis),
                out_specs=PS(data_axis),
                check_vma=False,
            )
        )
        x = jnp.ones((P * n,), jnp.float32)
        times.append(_time_call(fn, x, repeats=repeats))
    # least-squares t = alpha + beta * S over the probe sizes
    m = len(payload_bytes)
    sx = sum(float(s) for s in payload_bytes)
    sy = sum(times)
    sxx = sum(float(s) ** 2 for s in payload_bytes)
    sxy = sum(float(s) * t for s, t in zip(payload_bytes, times))
    denom = m * sxx - sx * sx
    beta = (m * sxy - sx * sy) / denom if denom else base.beta
    alpha = (sy - beta * sx) / m
    alpha = min(max(alpha, 1e-8), 1.0)
    beta = min(max(beta, 1e-13), 1e-3)

    nmm = 512
    a = jnp.ones((nmm, nmm), jnp.float32)
    t_mm = _time_call(jax.jit(lambda u: u @ u), a, repeats=repeats)
    flops = 2.0 * nmm**3 / max(t_mm, 1e-9)
    flops = min(max(flops, 1e9), 1e16)

    fitted = HockneyModel(alpha=alpha, beta=beta, flops_per_s=flops)
    _CALIBRATION_CACHE[cache_key] = fitted
    return fitted
