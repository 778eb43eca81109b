"""Runs of the four-chip cell at a small size on four virtual CPU devices,
sound and with the timed path broken underneath: one JSON line per case.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 chipbench/tests/x4_worker.py [case ...]

``test_distributed.py`` starts it, so that the other tests keep one device.
"""

import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH, HERE]

import jax  # noqa: E402

import control  # noqa: E402
import run  # noqa: E402
from repro.api import Counter  # noqa: E402
from test_run import altered, half_batch, stale  # noqa: E402

CELL = "g500s20-u12-2-x4"
SEED = 2**33 + 12345


def small_spec():
    spec = run.load_cell(CELL)
    spec["config"]["graph"]["scale"] = 10
    spec["traffic"]["batch_cap"] = 2
    spec["traffic"]["check_colorings"] = 4
    return spec


@contextlib.contextmanager
def patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def stream_fault(fault):
    real = Counter.sample_stream

    def broken(self, key=None, *, batch=8):
        return fault(real(self, key, batch=batch))

    return patched(Counter, "sample_stream", broken)


def one_key_rule(backend, key, batch, slot, n, n_pad, k):
    """A wrong coloring rule: one draw of ``[batch, n]`` from the batch key,
    as the single backend draws, in place of a key per slot."""
    import jax.numpy as jnp

    return jax.random.randint(key, (batch, n), 0, k, dtype=jnp.int32)[slot]


@contextlib.contextmanager
def no_exchange():
    """The exchange between chips left out: every all-to-all and ppermute
    hands each chip its own rows back."""
    keep = lambda x, *args, **kw: x
    with patched(jax.lax, "all_to_all", keep), patched(jax.lax, "ppermute", keep):
        yield


CASES = {
    "sound": contextlib.nullcontext,
    "wrong_coloring_rule": lambda: patched(run, "coloring_of", one_key_rule),
    "stale": lambda: stream_fault(stale),
    "half_batch": lambda: stream_fault(half_batch),
    "altered": lambda: stream_fault(altered),
    "no_exchange": no_exchange,
}


def main(names):
    assert len(jax.devices()) >= 4, jax.devices()
    for name in names:
        if name == "program_bytes":
            spec = small_spec()
            key = run.seed_key(SEED, run.WARM)
            cell = run.build(spec, key, require_tpu=False)
            out = {"case": name, "bytes": [run.program_bytes(cell.counter, key, b) for b in (1, 2)],
                   "warm": cell.counter.sample_fn(key, 2).tolist()}
            run.free_program(cell)
        elif name == "control":
            rows = control.readings(small_spec(), [1, 2, 3], controls=("bfloat16",),
                                    require_tpu=False)
            out = {"case": name, "limit": small_spec()["limits"]["rel_gap"], "rows": rows}
        else:
            with CASES[name]():
                res = run.run_cell(small_spec(), SEED, 1.0, False, require_tpu=False)
            out = {"case": name, "result": res, "live_arrays": len(jax.live_arrays())}
        print(json.dumps(out, default=float), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or [*CASES, "program_bytes", "control"])
