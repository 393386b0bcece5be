"""On the chip, by hand: the one-step recurrence of a Mamba-2 layer alone,
the Pallas kernel (`ray_tpu/ops/ssm_step.py`) against the jnp form it
replaces there (`ssm_step.ssm_step_reference`: the new state stored with a
dynamic-update-slice, as `mamba2.step` does off the chip), on the state buffers of the two cells
that run it: granite-4.0-h-small's `f32[9, 64, 128, 64, 128]` (one group)
and the nemotron cut's `f32[8, 32, 64, 64, 128]` (eight), in one process
(PERF.md section 6, PR 49).

    chiprun -- python benchmark/selftest/chip_ssm_step.py

A launch steps every layer of the buffer once, each layer's input taking
the layer before's read-out (a decode program's share of the recurrence;
one layer a launch is under the host's own time a launch at the nemotron
sizes); 40 launches are queued back to back under the host's clock.
Lines: ms a layer and GB/s of the state's one read and one write
(`benchmark/flops_ssm.py: state_bytes`). BLOCK_BYTES=<n> overrides the
kernel's bytes a block (tuning only). The numbers also go to
chiprun_out/ssm_step.json."""
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.flops_ssm import state_bytes  # noqa: E402
from ray_tpu.ops import ssm_step  # noqa: E402

OUT = {}


def timed(f, buf, *args, reps=40):
    for _ in range(2):
        buf, y = f(buf, *args)
    jax.block_until_ready((buf, y))
    t0 = time.perf_counter()
    for _ in range(reps):
        buf, y = f(buf, *args)
    jax.block_until_ready((buf, y))
    return (time.perf_counter() - t0) / reps


def case(name, L, slots, H, P, N, G):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    operands = (jax.random.uniform(k[1], (slots, H), minval=0.9, maxval=1.0),
                0.01 * jax.random.normal(k[2], (slots, H, P)),
                jax.random.normal(k[3], (slots, G, N)),
                jax.random.normal(k[4], (slots, G, N)))

    def state():
        return jax.random.normal(k[0], (L, slots, H, P, N))

    # a layer's x dt takes the layer before's y, as a model's layers do:
    # independent layers would let XLA read every layer of the old buffer
    # first and copy the buffer to keep it (the AOT compile, PR 49)
    def by_jnp(buf, decay, xdt, B, C):
        y = jnp.zeros_like(xdt)
        for layer in range(L):
            buf, y = ssm_step.ssm_step_reference(
                buf, layer, decay, xdt + 1e-3 * y, B, C)
        return buf, y

    def by_kernel(buf, decay, xdt, B, C):
        y = jnp.zeros_like(xdt)
        for layer in range(L):
            buf, y = ssm_step.ssm_step(buf, layer, decay, xdt + 1e-3 * y,
                                       B, C)
        return buf, y

    res, outs = {"block": ssm_step.block_of(slots, H // G, P, N)}, {}
    for path, f in (("jnp", by_jnp), ("kernel", by_kernel)):
        f = jax.jit(f, donate_argnums=0)
        buf, y = f(state(), *operands)
        outs[path] = (np.asarray(buf[L - 1]), np.asarray(y))
        del buf
        s = timed(f, state(), *operands)
        res[path] = {"ms_a_layer": s / L * 1e3,
                     "GBps": L * state_bytes(slots, H, P, N) / s / 1e9}
    res["max_abs_diff"] = {part: float(np.max(np.abs(a - b))) for part, a, b
                           in zip(("state", "y"), *outs.values())}
    print(f"[ssm_step] {name} f32{[L, slots, H, P, N]} G={G}: "
          + json.dumps(res), flush=True)
    OUT[name] = res


if __name__ == "__main__":
    if "BLOCK_BYTES" in os.environ:
        ssm_step.BLOCK_BYTES = int(os.environ["BLOCK_BYTES"])
    print(jax.devices(), flush=True)
    case("granite-4.0-h-small", 9, 64, 128, 64, 128, 1)
    case("nemotron-3-nano-30b-a3b", 8, 32, 64, 64, 128, 8)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_step.json", "w") as f:
        json.dump(OUT, f, indent=1)
