"""Pipelined transformer — real multi-stage model wiring on a hybrid
dcn x pipe x fsdp x tensor mesh with ring attention.

Reference parity: Megatron-style pipeline-parallel transformer training
(megatron/core/pipeline_parallel/schedules.py interleaved 1F1B +
context parallelism). TPU-native shape:

- transformer BLOCKS are stacked on a leading virtual-stage axis and
  sharded over `pipe`; the interleaved circular schedule
  (parallel/pipeline.py pipeline_apply_interleaved) runs them with an
  (S-1)/(R*M) bubble;
- attention inside every block is RING ATTENTION over the `fsdp` axis:
  the sequence dim is context-parallel across the fsdp group (the
  reference's CP-over-DP-group layout) and kv blocks rotate on ICI;
- embed/head and the loss live OUTSIDE the manual region; jax 0.9
  shard_map(axis_names={"pipe", "fsdp"}) leaves the remaining mesh axes
  (dcn, data, tensor) to GSPMD, so the batch stays sharded over
  (dcn, data) and the block weight matrices over `tensor` with XLA
  inserting the collectives.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.ops.cross_entropy import cross_entropy
from ray_tpu.parallel.pipeline import pipeline_apply_interleaved
from ray_tpu.parallel.ring_attention import ring_attention


@dataclasses.dataclass(frozen=True)
class PipelinedConfig:
    vocab_size: int = 256
    n_virtual_stages: int = 4  # total blocks = virtual stages
    n_head: int = 4
    d_model: int = 64
    d_ff: int = 128
    block_size: int = 32
    num_microbatches: int = 4


def init_pipelined(key, cfg: PipelinedConfig) -> dict:
    """Stacked-block params: every block tensor has a leading
    (n_virtual_stages,) dim the caller shards over `pipe`."""
    V, D, F = cfg.n_virtual_stages, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 8)

    def n(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    s = 0.02
    return {
        "embed": n(ks[0], (cfg.vocab_size, D), s),
        "pos": n(ks[1], (cfg.block_size, D), s),
        "blocks": {
            "qkv": n(ks[2], (V, D, 3 * D), s),
            "attn_out": n(ks[3], (V, D, D), s),
            "fc": n(ks[4], (V, D, F), s),
            "proj": n(ks[5], (V, F, D), s),
        },
        "ln_f": jnp.ones((D,)),
        "head": n(ks[6], (D, cfg.vocab_size), s),
    }


def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _block(cfg: PipelinedConfig, params, h):
    """One transformer block; h is the LOCAL (mb, t, D) shard with the
    sequence dim context-parallel over `fsdp` (ring attention)."""
    mb, t, D = h.shape
    H = cfg.n_head
    qkv = _rms(h) @ params["qkv"]  # (mb, t, 3D)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(mb, t, H, D // H)
    k = k.reshape(mb, t, H, D // H)
    v = v.reshape(mb, t, H, D // H)
    att = ring_attention(q, k, v, "fsdp", causal=True)
    h = h + att.reshape(mb, t, D) @ params["attn_out"]
    h = h + jax.nn.gelu(_rms(h) @ params["fc"]) @ params["proj"]
    return h


def pipelined_loss(params, batch, cfg: PipelinedConfig, mesh,
                   num_repeats: int | None = None):
    """Full forward + next-token loss. Blocks run under
    shard_map(axis_names={pipe, fsdp}); everything else is GSPMD."""
    pipe = dict(mesh.shape).get("pipe", 1)
    R = num_repeats or max(1, cfg.n_virtual_stages // pipe)
    tokens, targets = batch["tokens"], batch["targets"]
    h = params["embed"][tokens] + params["pos"][None, :tokens.shape[1]]

    def body(blocks, hh):
        # hh: (B_local, t_local, D) — batch auto-sharded (dcn/data),
        # sequence manually sharded over fsdp. Microbatching splits the
        # LOCAL batch; blocks: this pipe rank's (R, ...) virtual stages.
        return pipeline_apply_interleaved(
            partial(_block, cfg), blocks, hh, "pipe",
            num_microbatches=cfg.num_microbatches, num_repeats=R)

    # round-robin virtual-stage placement: stage v -> (rank v % S, slot
    # v // S); reorder the stacked dim so shard_map's contiguous split
    # hands rank s exactly its slots in order
    S = pipe
    order = jnp.argsort(jnp.arange(cfg.n_virtual_stages) % S, stable=True)
    blocks = jax.tree.map(lambda p: p[order], params["blocks"])
    sm = jax.shard_map(body, mesh=mesh, axis_names={"pipe", "fsdp"},
                       in_specs=(P("pipe"), P(None, "fsdp", None)),
                       out_specs=P(None, "fsdp", None), check_vma=False)
    h = sm(blocks, h)
    logits = _rms(h * params["ln_f"]) @ params["head"]
    return cross_entropy(logits, targets)


# ---------------------------------------------------------------------------
# MPMD stage split — the 1F1B worker-group strategy's model face
# ---------------------------------------------------------------------------


def split_pipeline_stages(params, cfg: PipelinedConfig,
                          num_stages: int) -> list[dict]:
    """Split a full pipelined-param tree into `num_stages` contiguous
    stage subtrees for the MPMD strategy (train/pipeline_strategy.py):
    stage s gets blocks[V*s//S : V*(s+1)//S]; stage 0 additionally owns
    embed/pos, the last stage ln_f/head. Union of stages == the full
    tree, so a single-program run of the same params is the parity
    reference."""
    V, S = cfg.n_virtual_stages, num_stages
    if not 1 <= S <= V:
        raise ValueError(f"need 1 <= stages <= {V} blocks, got {S}")
    stages = []
    for s in range(S):
        lo, hi = V * s // S, V * (s + 1) // S
        stage = {"blocks": jax.tree.map(lambda p: p[lo:hi],
                                        params["blocks"])}
        if s == 0:
            stage["embed"], stage["pos"] = params["embed"], params["pos"]
        if s == S - 1:
            stage["ln_f"], stage["head"] = params["ln_f"], params["head"]
        stages.append(stage)
    return stages


def merge_pipeline_stages(stages: list[dict]) -> dict:
    """Inverse of `split_pipeline_stages` (checkpointing / parity)."""
    blocks = jax.tree.map(
        lambda *leaves: jnp.concatenate(leaves, axis=0),
        *[st["blocks"] for st in stages])
    return {"embed": stages[0]["embed"], "pos": stages[0]["pos"],
            "blocks": blocks, "ln_f": stages[-1]["ln_f"],
            "head": stages[-1]["head"]}


def split_pipeline_stages_interleaved(params, cfg: PipelinedConfig,
                                      num_stages: int, num_repeats: int
                                      ) -> list[list[dict]]:
    """Round-robin virtual-stage split for the interleaved MPMD
    strategy: the model becomes V = S*R virtual chunks (contiguous
    block runs, split exactly like `split_pipeline_stages(.., V)`), and
    worker s owns chunks [s, s+S, .., s+(R-1)S] — result[s][r] is
    virtual stage r*S + s. Chunk 0 carries embed/pos (it lives on
    worker 0), chunk V-1 carries ln_f/head (worker S-1), so each chunk
    is directly usable with `stage_apply(.., stage_idx=v,
    num_stages=V, ..)`."""
    V = num_stages * num_repeats
    chunks = split_pipeline_stages(params, cfg, V)
    return [[chunks[r * num_stages + s] for r in range(num_repeats)]
            for s in range(num_stages)]


def merge_pipeline_stages_interleaved(stage_chunks: list[list[dict]]
                                      ) -> dict:
    """Inverse of `split_pipeline_stages_interleaved`: reassemble the
    full tree from per-worker chunk lists (checkpointing / parity)."""
    S, R = len(stage_chunks), len(stage_chunks[0])
    flat = [stage_chunks[v % S][v // S] for v in range(S * R)]
    return merge_pipeline_stages(flat)


def _local_mesh():
    """One-device mesh carrying the `fsdp` axis so `_block`'s ring
    attention resolves outside the hybrid-mesh program (size-1 ring ==
    plain causal attention, numerically the same blockwise softmax)."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("fsdp",))


def stage_apply(cfg: PipelinedConfig, stage_params: dict, stage_idx: int,
                num_stages: int, payload, targets=None, mesh=None):
    """One pipeline stage's forward: tokens -> h for stage 0, h -> h in
    the middle, h -> scalar loss (or logits when `targets` is None) on
    the last stage. Runs the SAME `_block` math as `pipelined_loss`
    (under a size-1 fsdp shard_map), so chaining all stages reproduces
    the single-program loss bit-for-bit modulo float reassociation.
    Differentiable — the MPMD strategy takes jax.vjp of this per
    microbatch. A `mesh` carrying a `data` axis (the strategy's
    intra-stage ZeRO data-parallel group) splits the microbatch over it
    — block weights stay replicated (or ZeRO-resharded by the caller)
    and GSPMD inserts the loss-mean reduction."""
    from ray_tpu.parallel.ops import shard_map as _shard_map
    from jax.sharding import PartitionSpec as P

    first, last = stage_idx == 0, stage_idx == num_stages - 1
    if first:
        tokens = payload
        h = stage_params["embed"][tokens] \
            + stage_params["pos"][None, :tokens.shape[1]]
    else:
        h = payload
    mesh = mesh if mesh is not None else _local_mesh()
    bspec = P("data") if dict(mesh.shape).get("data", 1) > 1 else P()

    def body(blocks, hh):
        def one(carry, blk):
            return _block(cfg, blk, carry), None

        out, _ = jax.lax.scan(one, hh, blocks)
        return out

    h = _shard_map(body, mesh, in_specs=(P(), bspec), out_specs=bspec)(
        stage_params["blocks"], h)
    if not last:
        return h
    logits = _rms(h * stage_params["ln_f"]) @ stage_params["head"]
    if targets is None:
        return logits
    return cross_entropy(logits, targets)


def pipelined_shardings(params, cfg: PipelinedConfig, mesh):
    """NamedShardings: block stacks over pipe (+ tensor on the wide
    dim), embed/head over tensor, rest replicated."""
    def spec(path, leaf):
        name = path[-1] if path else ""
        if name in ("qkv", "fc"):
            return P("pipe", None, "tensor")
        if name in ("attn_out", "proj"):
            return P("pipe", "tensor", None)
        if name in ("embed", "head"):
            return P(None, "tensor")
        return P()

    def walk(tree, path):
        if isinstance(tree, dict):
            return {k: walk(v, path + [k]) for k, v in tree.items()}
        return NamedSharding(mesh, spec(path, tree))

    return walk(params, [])


def pipelined_train_step(cfg: PipelinedConfig, mesh, lr: float = 1e-2):
    """(params, batch) -> (params, loss) SGD step, jitted over the
    hybrid mesh."""

    @jax.jit
    def step(params, batch):
        loss, grads = jax.value_and_grad(pipelined_loss)(
            params, batch, cfg, mesh)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return params, loss

    return step
