"""Worker (8 host devices): collective bytes of fused vs ring FSDP gather
and fp32 vs int8 gradient reduce-scatter, from compiled HLO + wall clock."""

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.comm import compressed_ring_reduce_scatter, ring_allgather, ring_reduce_scatter
from repro.compat import make_mesh


def _mesh():
    return make_mesh((8,), ("data",))


def _coll_bytes(compiled):
    txt = compiled.as_text()
    out = {}
    for kind in (
        "all-gather",
        "all-reduce",
        "reduce-scatter",
        "all-to-all",
        "collective-permute",
    ):
        total = 0
        for m in re.finditer(rf"= (\w+)\[([\d,]*)\][^\n]*? {kind}(?:-start)?\(", txt):
            dims = m.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * {"f32": 4, "bf16": 2, "s8": 1, "int8": 1}.get(m.group(1), 4)
        out[kind] = total
    return out


def main():
    mesh = _mesh()
    w = np.random.default_rng(0).standard_normal((8, 1024, 512)).astype(np.float32)

    # fused all-gather
    fused = jax.jit(
        shard_map(
            lambda x: jax.lax.all_gather(x[0], "data"),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
    )
    cf = fused.lower(w).compile()
    bf = _coll_bytes(cf)
    print(f"lm_coll/fsdp_gather/fused,0.0,bytes={bf}")

    # relay ring
    ring = jax.jit(
        shard_map(
            lambda x: ring_allgather(x[0], "data"),
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
    )
    cr = ring.lower(w).compile()
    br = _coll_bytes(cr)
    print(f"lm_coll/fsdp_gather/ring,0.0,bytes={br}")

    # gradient reduce-scatter: fp32 vs int8 payloads
    g = np.random.default_rng(1).standard_normal((8, 8, 2048)).astype(np.float32)
    rs32 = jax.jit(
        shard_map(
            lambda x: ring_reduce_scatter(x[0], "data")[None],
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
    )
    rs8 = jax.jit(
        shard_map(
            lambda x: compressed_ring_reduce_scatter(x[0], "data")[None],
            mesh=mesh,
            in_specs=P("data"),
            out_specs=P("data"),
        )
    )
    b32 = _coll_bytes(rs32.lower(g).compile())
    b8 = _coll_bytes(rs8.lower(g).compile())
    cp32 = b32["collective-permute"]
    cp8 = b8["collective-permute"]
    ratio = cp32 / max(cp8, 1)
    print(f"lm_coll/grad_rs/fp32,0.0,permute_bytes={cp32}")
    print(f"lm_coll/grad_rs/int8,0.0,permute_bytes={cp8} compression={ratio:.2f}x")

    # numerical error of the compressed path
    want = g.sum(axis=0)
    got = np.asarray(rs8(jnp.asarray(g)))
    rel = np.abs(got - want).max() / np.abs(want).max()
    print(f"lm_coll/grad_rs/int8_rel_err,0.0,rel={rel:.4f}")


if __name__ == "__main__":
    main()
