"""What can be held without a chip: every pallas entry point lowers for
the TPU, the flash kernel is right under shard_map, chip_smoke.py refuses
to pass on the CPU, and the compile cache lands where the rule says.

None of this shows that anything runs on a chip — `chip_smoke.py` through
the chip tool does. Cross-lowering is the check that caught a paged kernel
whose blocks Mosaic refuses (it had only ever run in interpret mode);
tests/test_kv_pool_layout.py compiles the kernel that reads the cached
context now, inside the serve programs, for a described v5e."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lowers_for_tpu(fn, *args):
    """Trace on the CPU, lower for the TPU: runs the pallas->Mosaic
    lowering (block shapes, layouts, supported ops) with no device."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    return text


# (H, H_kv, D): gpt2-small, and a grouped-query shape
SHAPES = {"gpt2-small": (12, 12, 64), "gqa": (32, 8, 128)}
# the flash kernel's three layouts: two heads a 128-lane block, one head a
# block, and (H odd at D = 64) the heads moved beside the batch
FLASH_SHAPES = dict(SHAPES, **{"gpt2-odd-heads": (3, 3, 64)})


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
def test_flash_attention_lowers_for_tpu(shape):
    from ray_tpu.ops.flash_attention import flash_attention

    H, _, D = FLASH_SHAPES[shape]
    q = jax.ShapeDtypeStruct((2, 1024, H, D), jnp.bfloat16)
    _lowers_for_tpu(flash_attention, q, q, q)

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = _lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dkv


@pytest.mark.parametrize("width", [1, 5])  # decode, and verify at K=4
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_paged_attention_lowers_for_tpu(shape, width):
    from ray_tpu.ops.paged_attention import paged_attention
    from ray_tpu.serve.llm.cache import KVLayout

    H, HK, D = SHAPES[shape]
    S, L, pages, bs, max_blocks = 8, 4, 256, 16, 64
    layout = KVLayout(L, pages, bs, HK, D)
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((S, width, HK, H // HK, D), bf16)
    own = jax.ShapeDtypeStruct((S, width, HK, D), bf16)
    own_valid = jax.ShapeDtypeStruct((S, width, width), jnp.bool_)
    pool = jax.ShapeDtypeStruct(layout.shape, bf16)
    tables = jax.ShapeDtypeStruct((S, max_blocks), jnp.int32)
    ctx = jax.ShapeDtypeStruct((S,), jnp.int32)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    _lowers_for_tpu(
        lambda q, ok, ov, seen, kp, vp, t, c, l: paged_attention(
            q, ok, ov, seen, kp, vp, t, c, layout=layout, layer=l,
            dtype=bf16),
        q, own, own, own_valid, pool, pool, tables, ctx, layer)


# (heads, groups): granite_hybrid's mixer and nemotron_h's, at their widths
SSM_SHAPES = {"one-group": (128, 1), "eight-groups": (64, 8)}


@pytest.mark.parametrize("shape", sorted(SSM_SHAPES))
def test_ssm_step_lowers_for_tpu(shape):
    from ray_tpu.ops.ssm_step import ssm_step

    H, G = SSM_SHAPES[shape]
    layers, slots, P, N = 2, 8, 64, 128
    f32 = jnp.float32
    text = _lowers_for_tpu(
        lambda buf, decay, xdt, B, C: ssm_step(buf, 1, decay, xdt, B, C),
        jax.ShapeDtypeStruct((layers, slots, H, P, N), f32),
        jax.ShapeDtypeStruct((slots, H), f32),
        jax.ShapeDtypeStruct((slots, H, P), f32),
        jax.ShapeDtypeStruct((slots, G, N), f32),
        jax.ShapeDtypeStruct((slots, G, N), f32))
    # the state buffer is the kernel's first result, in place
    assert "output_tuple_indices = [0], operand_index = 5" in text


def test_paged_attention_layer_of_whole_pool_matches_reference():
    """The models hand the kernel the whole pool, as its KVLayout shapes
    it, and a traced layer index (slicing the pool per layer, or
    reshaping it, would copy it)."""
    from ray_tpu.ops.context_attention import causal_rows
    from ray_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_reference,
    )
    from ray_tpu.serve.llm.cache import KVLayout

    rng = np.random.RandomState(1)
    S, W, H, HK, D, bs, maxB, pages, L = 2, 3, 4, 2, 16, 4, 5, 24, 3
    layout = KVLayout(L, pages, bs, HK, D)
    kp = rng.normal(size=layout.shape).astype(np.float32)
    vp = rng.normal(size=layout.shape).astype(np.float32)
    tables = rng.permutation(np.arange(1, pages))[:S * maxB] \
        .reshape(S, maxB).astype(np.int32)
    ctx = np.asarray([9, maxB * bs], np.int32)
    q = rng.normal(size=(S, W, HK, H // HK, D)).astype(np.float32)
    ok = rng.normal(size=(S, W, HK, D)).astype(np.float32)
    ov = rng.normal(size=(S, W, HK, D)).astype(np.float32)
    operands = (q, ok, ov, causal_rows(jnp.ones((S, W), bool)),
                jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
                jnp.asarray(ctx))
    for layer in range(L):
        out = jax.jit(lambda l: paged_attention(
            *operands, layout=layout, layer=l, dtype=jnp.float32,
            interpret=True))(jnp.int32(layer))
        # the oracle on the same pool, and on the layer's rows spelled
        # out with numpy: (pages, bs, HK * D) -> heads of D lanes
        ref = paged_attention_reference(*operands, layout=layout,
                                        layer=layer, dtype=jnp.float32)
        k_ctx = kp[layer][tables].reshape(S, maxB * bs, HK, D)
        np.testing.assert_array_equal(
            np.asarray(layout.read(kp, layer, tables)), k_ctx)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)


def test_flash_under_shard_map_matches_reference(cpu_mesh8):
    """GSPMD cannot partition a Mosaic kernel; on a mesh the dispatcher
    runs it per shard — batch over (data, fsdp), heads over tensor.
    Forward and gradients against the einsum, interpret mode, 8 devices."""
    from ray_tpu.ops.attention import (
        causal_attention_reference,
        sharded_flash_attention,
    )

    B, T, H, D = 4, 256, 4, 32
    q, k, v, g = (jax.random.normal(kk, (B, T, H, D), jnp.float32)
                  for kk in jax.random.split(jax.random.PRNGKey(0), 4))

    def sharded(q, k, v):
        return sharded_flash_attention(q, k, v, cpu_mesh8, interpret=True)

    def weighted(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * g)

    with jax.set_mesh(cpu_mesh8):
        out = jax.jit(sharded)(q, k, v)
        grads = jax.jit(jax.grad(weighted(sharded),
                                 argnums=(0, 1, 2)))(q, k, v)
    # really sharded: batch 4 ways, heads 2 ways
    assert out.sharding.shard_shape(out.shape) == (1, T, 2, D)
    np.testing.assert_allclose(
        out, causal_attention_reference(q, k, v), atol=2e-5)
    ref = jax.grad(weighted(causal_attention_reference),
                   argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(grads, ref):
        np.testing.assert_allclose(got, want, atol=5e-5)


def test_chip_smoke_without_a_chip_fails_fast_and_says_why():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=60, env=env)
    assert p.returncode != 0
    last = p.stdout.strip().splitlines()[-1]
    assert "no TPU chip" in last, p.stdout
    assert '"ok"' not in p.stdout


# ------------------------------------------------------- compile cache rule

_REPORT = (
    "import json, os, sys\n"
    "import ray_tpu, jax\n"
    "from ray_tpu import _compile_cache as cc\n"
    "print(json.dumps({'env': os.environ.get(cc.ENV),\n"
    "                  'jax': jax.config.jax_compilation_cache_dir,\n"
    "                  'rule': cc.configure()}))\n")


def _child_report(code, without, **variables):
    """The JSON a child process prints last, run from the checkout with
    this environment less `without`, plus `variables`."""
    env = {k: v for k, v in os.environ.items() if k not in without}
    env.update(variables)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _cache_report(env_value):
    given = {} if env_value is None \
        else {"JAX_COMPILATION_CACHE_DIR": env_value}
    return _child_report(_REPORT, ("JAX_COMPILATION_CACHE_DIR",), **given)


def test_compile_cache_placed_from_outside_is_left_alone(tmp_path,
                                                         monkeypatch):
    outside = str(tmp_path / "cache")
    r = _cache_report(outside)
    assert r == {"env": outside, "jax": outside, "rule": outside}
    # ...and in-process: no jax.config write when the variable is set
    from ray_tpu import _compile_cache as cc

    monkeypatch.setenv(cc.ENV, outside)
    writes = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: writes.append(a))
    assert cc.configure() == outside
    assert writes == []


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    a, b = _cache_report(None), _cache_report(None)  # two processes
    want = os.path.join(ROOT, ".jax_cache")
    assert a == b == {"env": want, "jax": want, "rule": want}
    import tempfile

    for volatile in (tempfile.gettempdir(), str(os.getpid()), "session_"):
        assert volatile not in want


def test_compile_cache_reaches_a_process_that_imported_jax_first():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    code = ("import jax\nassert jax.config.jax_compilation_cache_dir is None\n"
            "import ray_tpu\nprint(jax.config.jax_compilation_cache_dir)\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip().splitlines()[-1] == \
        os.path.join(ROOT, ".jax_cache"), out.stderr[-2000:]


_FLOOR_REPORT = (
    "import json, os, sys\n"
    "{first}\n"
    "import ray_tpu, jax\n"
    "from ray_tpu import _compile_cache as cc\n"
    "print(json.dumps({{\n"
    "    'env': os.environ.get(cc.FLOOR_ENV),\n"
    "    'jax': jax.config.jax_persistent_cache_min_compile_time_secs}}))\n")

# (what the process finds in its environment, what it runs before
# `import ray_tpu`) -> (the variable after the import, jax's floor)
_FLOOR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"
FLOOR_CASES = {
    # set from outside: left alone, whatever the platform
    "from-outside": ({_FLOOR: "0.25"}, "", ("0.25", 0.25)),
    "from-outside-on-the-cpu": (
        {_FLOOR: "0.25", "JAX_PLATFORMS": "cpu"}, "", ("0.25", 0.25)),
    # unset and not pinned to the CPU: every compile is kept, and the
    # children inherit the variable
    "unset": ({}, "", ("0", 0.0)),
    "unset-on-a-chip": ({"JAX_PLATFORMS": "tpu,cpu"}, "", ("0", 0.0)),
    "unset-jax-imported-first": (
        {},
        "import jax\n"
        "assert jax.config.jax_persistent_cache_min_compile_time_secs == 1",
        ("0", 0.0)),
    # pinned to the CPU (tier-1): jax's own second stays, or a test run
    # writes some ten thousand files into the checkout
    "cpu": ({"JAX_PLATFORMS": "cpu"}, "", (None, 1.0)),
    "cpu-pinned-through-jax-config": (
        {}, "import jax; jax.config.update('jax_platforms', 'cpu')",
        (None, 1.0)),
}


@pytest.mark.parametrize("case", sorted(FLOOR_CASES))
def test_compile_cache_floor(case):
    """`jax_persistent_cache_min_compile_time_secs` by the rule of
    `ray_tpu/_compile_cache.py`. No backend is initialised: the child only
    reads what was configured."""
    given, first, (env_out, floor) = FLOOR_CASES[case]
    report = _child_report(_FLOOR_REPORT.format(first=first),
                           (_FLOOR, "JAX_PLATFORMS"), **given)
    assert report == {"env": env_out, "jax": floor}


def test_compile_cache_floor_from_outside_writes_no_jax_config(monkeypatch):
    from ray_tpu import _compile_cache as cc

    monkeypatch.setenv(cc.FLOOR_ENV, "0.25")
    monkeypatch.setenv(cc.ENV, "/somewhere/else")
    writes = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: writes.append(a))
    cc.configure()
    assert writes == [] and os.environ[cc.FLOOR_ENV] == "0.25"


# ------------------------------------------ what the chip turned up (PR 21)

_PAUSED_DRIVER = '''
import sys, time
import ray_tpu

ray_tpu.init(num_cpus=2)

@ray_tpu.remote
class A:
    def ping(self):
        return "pong"

a = A.remote()
assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
print("READY", flush=True)
sys.stdin.readline()  # the parent stops and continues this process first
time.sleep(1.5)  # monitor and heartbeat threads both get to run
print("RESULT", ray_tpu.get(a.ping.remote(), timeout=30),
      [n["Alive"] for n in ray_tpu.nodes()], flush=True)
ray_tpu.shutdown()
'''


def test_node_survives_a_pause_of_the_process_that_hosts_the_head():
    """A TPU worker opening its chips froze the chip machine's other
    processes for ~4.5 s (measured, PR 21); with head, nodelet and driver
    in one process that read as 5 s without heartbeats and the head
    declared its own node dead, killing the train gang at bring-up. A
    failure detector that was itself paused credits the pause."""
    import signal
    import time

    p = subprocess.Popen([sys.executable, "-c", _PAUSED_DRIVER], cwd=ROOT,
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True)
    try:
        assert p.stdout.readline().strip() == "READY", p.stderr.read()[-2000:]
        os.kill(p.pid, signal.SIGSTOP)
        time.sleep(6.5)  # longer than NODE_DEATH_AFTER_S
        os.kill(p.pid, signal.SIGCONT)
        p.stdin.write("\n")
        p.stdin.flush()
        out, err = p.communicate(timeout=90)
    finally:
        if p.poll() is None:
            p.kill()
    assert "RESULT pong [True]" in out, (out, err[-2000:])
