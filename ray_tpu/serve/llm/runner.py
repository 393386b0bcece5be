"""ModelRunner: jit-compiled paged prefill / decode steps.

Owns the device-side half of the KV cache (a K and a V pool for each
kind of layer that has keys and values, `ModelAdapter.kv_kinds`: one kind
for most families, a full and a window kind for mimo_v2; laid out, read
and written only through `cache.KVLayout`), for a family with
recurrent layers also the lanes' state buffers (`cache.StateLayout`,
read and written through `cache.StateView`), and the compiled programs
that touch them:

- **prefill**: full-sequence forward of one prompt (padded to a length
  bucket), storing its K/V a page at a time (a bucket is whole pages,
  `KVLayout.write_pages`) and sampling the first generated token from
  the last valid position's logits; a **chunk** does the same from a
  page's edge against the context already cached;
- **decode**: one token for a batch of sequences (padded to a batch
  bucket), reading each lane's pages through its block table layer by
  layer, attending with a validity mask, scattering the new K/V at the
  lane's current position, and sampling the next token.

Shapes are **bucketed** so the number of XLA compilations is bounded:
prompt lengths round up to powers of two between
``prefill_bucket_min`` and ``max_model_len``; decode batches round up
to powers of two up to ``max_batch_size``; block tables are always
padded to the fixed width ``max_blocks_per_seq``. Total programs =
#length-buckets + #batch-buckets.

The table's width is the program's shape, not what it reads: a lane's
cached context is read in tiles of whole pages (`KVLayout.tile_pages`)
and only as many as a small group of lanes reaches, a count the program
takes from the lanes' positions (a chunk's `start`) at run time
(ops/context_attention.py). A decode program's rows are the step's lanes
by position, longest first, in groups of `lanes_per_group` consecutive
rows, so that a short lane is not read to a long one's length; `collect`
hands the results back in the caller's order. Where
`context_attention.reads_by_kernel` allows (a decode's or a verify's
few rows a lane, on a TPU) a Pallas kernel reads each lane to its own
length instead, a page the unit (ops/paged_attention.py).
`context_slots` counts what was read by the path taken, what of it was
valid and what a read to ``max_model_len`` would have been.

Padded lanes, and the pages of a prompt's or a chunk's bucket that hold
no valid row, point at **page 0** (the pool's null sink), so every
gather/scatter is in-bounds; the attention mask keeps null-page garbage,
and the padded rows behind a sequence's frontier in its own last page
(cache.py's head), out of the softmax. `rows_written` counts the valid
rows stored, by kind of KV layer and by path (a page at a time, or row
by row: decode, verify).

A program is **launched** (`launch_prefill`, `launch_chunk`,
`launch_decode`: inputs prepared, the jitted call made, nothing read)
and **collected** (`collect`: the wait for it and the copy to the host)
apart, so that the engine can enqueue the next program before it reads
this one's results; `prefill`, `prefill_chunk` and `decode` are the two
in one call. What the next program needs of this one's results, the
sampled ids, stays on the device: `slot_tokens` holds the last sampled
id of every lane slot, each of these programs writes its `nxt` there,
and a decode lane whose token the host has not read yet (`token` -1)
takes it from its slot. What a launch takes from the HOST is one int32
array, its **pack** (`pack_layout`: tokens, positions, page ids, tables,
the sampling fields and the step's count side by side, a float32 by its
bits; filled where it lies, taken apart by static slices in the program's
first lines): the runtime transfers every host argument apart, and a
transfer costs what it costs whatever it holds (0.13-0.17 ms on a v5e's
host, PERF.md §5). Every jitted call and every read is accounted for
by kind of program (`launch`: the call alone and the host arrays it was
handed; `fetch`: the wait for the program apart from the copy after it
and the rows put back in order).

With a mesh, parameters are sharded via the model's own
`parallel/sharding.py` partition rules and the cache pages are sharded
over the ``tensor`` axis by whole KV heads; calls run under
``jax.set_mesh(mesh)`` so in-model `constrain` calls resolve (same idiom
as train/spmd.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
import time
from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu.ops import context_attention, ssm_step
from ray_tpu.ops.context_attention import CachedContext
from ray_tpu.serve.llm.cache import (
    KVKind,
    KVLayout,
    StateLayout,
    StateView,
    blocks_by_kind,
)
from ray_tpu.util import tracing

# The step loop's phases, as `engine.stats()["step_phase_seconds"]` and
# as `llm.<phase>` events in a device profile. The engine times
# schedule, commit, emit, bookkeep, release (a read step's device
# results let go) and yield (the loop waiting for a swap or an abort to
# be done with the engine); the runner prepare, dispatch and fetch (the
# wait for the program, the copy, and the rows put back in the caller's
# order); the deployment's loop idle. Between them they hold a turn of
# the loop but for the statements between two phases, which
# `engine.stats()["loop"]` measures.
STEP_PHASES = ("schedule", "prepare", "dispatch", "fetch", "commit",
               "emit", "bookkeep", "idle", "release", "yield")


@dataclasses.dataclass(frozen=True)
class ModelAdapter:
    """Uniform view over a model family for the engine/runner."""

    name: str
    config_cls: type
    presets: dict[str, Callable[[], Any]]
    init_fn: Callable  # (key, cfg) -> params
    # (params, tokens, cfg) -> (logits, k, v), as every forward below; a
    # family with routed experts appends their pairs per layer and
    # expert, (L, n_experts) i32, which the programs hand on to the host.
    # k and v are stacked over the layers that have them; a family with
    # several kinds of such layers returns a tuple of stacks, one a kind
    prefill_fn: Callable
    # ctx: the lanes' cached context (ops/context_attention.py
    # CachedContext; a tuple of them, one a kind, for several kinds),
    # which the layers read as far as the lanes reach
    decode_fn: Callable  # (params, toks, pos, ctx, cfg) -> ...
    chunk_fn: Callable  # (params, toks, start, ctx, chunk_mask, cfg) -> ...
    rules_fn: Callable  # () -> PartitionRules
    # cfg -> the kinds of layer that HAVE keys and values (cache.KVKind:
    # layers, KV heads, K and V head widths, window): a pool pair and a
    # block table a kind, in this order, the first being the kind whose
    # pool `num_blocks` sizes
    kv_kinds: Callable[[Any], tuple]
    # recurrent state a lane carries beside its pages: cfg -> (layers
    # that have it, ((name, shape a lane and layer, dtype), ...)); None:
    # the family has none. With it, every forward above also takes
    # `state=` (a cache.StateView on the lane's or the lanes' slots) and
    # the two prefill forwards `n_valid=`, the rows that are not padding
    state_fn: Callable | None = None
    # cfg -> (offset, count) of the router's experts this replica holds,
    # for the routing account; None: all of them, or none
    held_experts: Callable | None = None
    # (params, cfg) -> the tree as the runner holds it: each leaf in the
    # dtype the forwards consume it in, one already there as the same
    # buffer (llama: `init_llama` creates every leaf in `param_dtype`)
    resident_fn: Callable = lambda params, cfg: params


def adapters() -> dict[str, ModelAdapter]:
    """Model registry: the nine families (lazy imports keep `import
    ray_tpu.serve` light; no family's module builds anything at
    import)."""
    from ray_tpu.models import (
        glm_dsa,
        gpt2,
        granite_hybrid,
        lfm2,
        ling3,
        llama,
        mimo_v2,
        nemotron_h,
        xing4,
    )

    def one_kind(layers, heads):
        """Every layer with keys and values alike, K as wide as V."""
        return lambda cfg: (KVKind("full", layers(cfg), heads(cfg),
                                   cfg.head_dim, cfg.head_dim),)

    return {
        "gpt2": ModelAdapter(
            name="gpt2",
            config_cls=gpt2.GPT2Config,
            presets={
                "tiny": gpt2.GPT2Config.tiny,
                "small": gpt2.GPT2Config.small,
                "medium": gpt2.GPT2Config.medium,
                "large": gpt2.GPT2Config.large,
                "xl": gpt2.GPT2Config.xl,
            },
            init_fn=gpt2.init_gpt2,
            prefill_fn=gpt2.gpt2_prefill_kv,
            decode_fn=gpt2.gpt2_decode_kv,
            chunk_fn=gpt2.gpt2_prefill_chunk_kv,
            rules_fn=gpt2.gpt2_partition_rules,
            kv_kinds=one_kind(lambda cfg: cfg.n_layer,
                              lambda cfg: cfg.n_head),
            resident_fn=gpt2.gpt2_resident_params,
        ),
        "llama": ModelAdapter(
            name="llama",
            config_cls=llama.LlamaConfig,
            presets={
                "tiny": llama.LlamaConfig.tiny,
                "small": llama.LlamaConfig.small,
                "olmoe_tiny": llama.LlamaConfig.olmoe_tiny,
                "olmoe_1b_7b": llama.LlamaConfig.olmoe_1b_7b,
                "olmoe_1b_7b_l8": llama.LlamaConfig.olmoe_1b_7b_l8,
            },
            init_fn=llama.init_llama,
            prefill_fn=llama.llama_prefill_kv,
            decode_fn=llama.llama_decode_kv,
            chunk_fn=llama.llama_prefill_chunk_kv,
            rules_fn=llama.llama_partition_rules,
            kv_kinds=one_kind(lambda cfg: cfg.n_layer,
                              lambda cfg: cfg.n_kv_head),
        ),
        "nemotron_h": ModelAdapter(
            name="nemotron_h",
            config_cls=nemotron_h.NemotronHConfig,
            presets={
                "tiny": nemotron_h.NemotronHConfig.tiny,
                "nano_30b_a3b_l18_ep4":
                    nemotron_h.NemotronHConfig.nano_30b_a3b_l18_ep4,
            },
            init_fn=nemotron_h.init_nemotron_h,
            prefill_fn=nemotron_h.nemotron_h_prefill_kv,
            decode_fn=nemotron_h.nemotron_h_decode_kv,
            chunk_fn=nemotron_h.nemotron_h_prefill_chunk_kv,
            rules_fn=nemotron_h.nemotron_h_partition_rules,
            kv_kinds=one_kind(lambda cfg: cfg.n_kv_layers,
                              lambda cfg: cfg.num_key_value_heads),
            state_fn=lambda cfg: (cfg.n_ssm_layers, cfg.state_parts()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
        "mimo_v2": ModelAdapter(
            name="mimo_v2",
            config_cls=mimo_v2.MimoV2Config,
            presets={
                "tiny": mimo_v2.MimoV2Config.tiny,
                "v2_5_l7_ep16": mimo_v2.MimoV2Config.v2_5_l7_ep16,
            },
            init_fn=mimo_v2.init_mimo_v2,
            prefill_fn=mimo_v2.mimo_v2_prefill_kv,
            decode_fn=mimo_v2.mimo_v2_decode_kv,
            chunk_fn=mimo_v2.mimo_v2_prefill_chunk_kv,
            rules_fn=mimo_v2.mimo_v2_partition_rules,
            kv_kinds=lambda cfg: tuple(KVKind(*k) for k in cfg.kv_kinds()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
        "glm_dsa": ModelAdapter(
            name="glm_dsa",
            config_cls=glm_dsa.GlmDsaConfig,
            presets={
                "tiny": glm_dsa.GlmDsaConfig.tiny,
                "glm_5_l5_ep32": glm_dsa.GlmDsaConfig.glm_5_l5_ep32,
            },
            init_fn=glm_dsa.init_glm_dsa,
            prefill_fn=glm_dsa.glm_dsa_prefill_kv,
            decode_fn=glm_dsa.glm_dsa_decode_kv,
            chunk_fn=glm_dsa.glm_dsa_prefill_chunk_kv,
            rules_fn=glm_dsa.glm_dsa_partition_rules,
            kv_kinds=lambda cfg: tuple(KVKind(*k) for k in cfg.kv_kinds()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
        "lfm2": ModelAdapter(
            name="lfm2",
            config_cls=lfm2.Lfm2Config,
            presets={
                "tiny": lfm2.Lfm2Config.tiny,
                "lfm2_8b_a1b_ep4": lfm2.Lfm2Config.lfm2_8b_a1b_ep4,
            },
            init_fn=lfm2.init_lfm2,
            prefill_fn=lfm2.lfm2_prefill_kv,
            decode_fn=lfm2.lfm2_decode_kv,
            chunk_fn=lfm2.lfm2_prefill_chunk_kv,
            rules_fn=lfm2.lfm2_partition_rules,
            kv_kinds=one_kind(lambda cfg: cfg.n_kv_layers,
                              lambda cfg: cfg.num_key_value_heads),
            state_fn=lambda cfg: (cfg.n_conv_layers, cfg.state_parts()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
        "granite_hybrid": ModelAdapter(
            name="granite_hybrid",
            config_cls=granite_hybrid.GraniteHybridConfig,
            presets={
                "tiny": granite_hybrid.GraniteHybridConfig.tiny,
                "h_small": granite_hybrid.GraniteHybridConfig.h_small,
                "h_small_l10_ep4":
                    granite_hybrid.GraniteHybridConfig.h_small_l10_ep4,
            },
            init_fn=granite_hybrid.init_granite_hybrid,
            prefill_fn=granite_hybrid.granite_hybrid_prefill_kv,
            decode_fn=granite_hybrid.granite_hybrid_decode_kv,
            chunk_fn=granite_hybrid.granite_hybrid_prefill_chunk_kv,
            rules_fn=granite_hybrid.granite_hybrid_partition_rules,
            kv_kinds=one_kind(lambda cfg: cfg.n_kv_layers,
                              lambda cfg: cfg.num_key_value_heads),
            state_fn=lambda cfg: (cfg.n_ssm_layers, cfg.state_parts()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
        "xing4": ModelAdapter(
            name="xing4",
            config_cls=xing4.Xing4Config,
            presets={
                "tiny": xing4.Xing4Config.tiny,
                "xing4_29b_a4b_l6_ep4":
                    xing4.Xing4Config.xing4_29b_a4b_l6_ep4,
            },
            init_fn=xing4.init_xing4,
            prefill_fn=xing4.xing4_prefill_kv,
            decode_fn=xing4.xing4_decode_kv,
            chunk_fn=xing4.xing4_prefill_chunk_kv,
            rules_fn=xing4.xing4_partition_rules,
            kv_kinds=lambda cfg: tuple(KVKind(*k) for k in cfg.kv_kinds()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
        "ling3": ModelAdapter(
            name="ling3",
            config_cls=ling3.Ling3Config,
            presets={
                "tiny": ling3.Ling3Config.tiny,
                "flash_l7_ep32": ling3.Ling3Config.flash_l7_ep32,
            },
            init_fn=ling3.init_ling3,
            prefill_fn=ling3.ling3_prefill_kv,
            decode_fn=ling3.ling3_decode_kv,
            chunk_fn=ling3.ling3_prefill_chunk_kv,
            rules_fn=ling3.ling3_partition_rules,
            kv_kinds=lambda cfg: tuple(KVKind(*k) for k in cfg.kv_kinds()),
            state_fn=lambda cfg: (cfg.n_kda_layers, cfg.state_parts()),
            held_experts=lambda cfg: (cfg.expert_offset, cfg.experts_held),
        ),
    }


def state_layout_of(adapter: ModelAdapter, cfg: Any,
                    slots: int) -> StateLayout | None:
    """The lanes' recurrent state for `slots` lane slots, as the family's
    adapter describes it; None for a family that has none."""
    if adapter.state_fn is None:
        return None
    layers, parts = adapter.state_fn(cfg)
    return StateLayout(layers, slots, parts)


class DecodeItem(NamedTuple):
    # last sampled token (input to this step); -1: not read from the
    # device yet, the program takes it from `slot`
    token: int
    pos: int  # its absolute position (== tokens written so far)
    # physical page ids, logical order; one such list a kind for a
    # family with several kinds of KV layer
    table: Sequence
    temperature: float
    top_k: int = 0  # 0: disabled
    top_p: float = 1.0  # 1.0: disabled
    # where the program leaves the id it samples for the next one to
    # read (index into `ModelRunner.slot_tokens`); -1: nowhere
    slot: int = -1


class Launched(NamedTuple):
    """A program on its way: its device outputs, nothing read."""

    kind: str  # "prefill" (a prompt's or a chunk's program) or "decode"
    results: tuple  # (nxt, logits), on the device
    aux: tuple  # the family's extras (routed experts' pairs)
    # decode: row j of the program is the caller's lane `order[j]` (the
    # real lanes, longest first); None: one row, a scalar id
    order: np.ndarray | None


# What the programs read of their lanes' cached context, in slots, by
# kind of program: `slots_read` as launched (tiles x tile x the lanes of
# a group; on the kernel's path whole pages to each lane's length; a
# window kind: its one tile a lane), `slots_valid` of them
# that a row can see (below a lane's length, and inside the window),
# `slots_reach` what a read from slot 0 to every lane's length would
# touch, `slots_full` what reading every row to `max_model_len` would
# (rows x slots a table holds). The monolithic prefill program reads none.
# A latent kind (`KVLayout.select`) reads two sorts of row: `slots_scored`
# are the indexer keys read (whole tiles, to a group's longest lane),
# `slots_selected` the cached slots a row can attend after the choice
# (the lesser of `select` and the lane's length, a lane), and `slots_read`
# the latent rows read: the same whole tiles, folded under the choice as
# their mask. Only a family with such a kind counts the two.
# A latent kind with NO indexer (`KVLayout.v_head_dim` 0) has every row
# attend every cached slot of its lane: `rows` are the real rows the
# programs ran (a decode step's lanes, a chunk's rows that are not
# padding) and `row_slots` the cached slots they attended, a row at a
# time (a lane's length a decode row, the chunk's offset a chunk row):
# their quotient says how long the contexts were. Only a family with
# such a kind counts the two.
CONTEXT_KINDS = ("decode", "prefill", "verify")
CONTEXT_COUNTS = ("slots_read", "slots_valid", "slots_reach", "slots_full")
SELECT_COUNTS = ("slots_scored", "slots_selected")
DENSE_LATENT_COUNTS = ("rows", "row_slots")

# the fields of a pack that hold the bits of a float32
PACK_FLOATS = ("temps", "topps")


@functools.lru_cache(maxsize=None)
def pack_layout(kind: str, rows: int, kinds: int, blocks: int
                ) -> tuple[int, dict[str, tuple[int, tuple]]]:
    """A launch's pack, the one int32 array a `kind` program ("prefill",
    "chunk", "decode", "verify") of `rows` (its bucket: `Tb`, `Sb`, `W`)
    takes from the host: (its length, {field: (offset, shape)}), the
    fields in the order the programs took them as arguments of their own.
    `kinds`: the kinds of KV layer, which lead the shape of a field that
    is one a kind; `blocks`: `max_blocks_per_seq`, the width of a table
    and of a prompt's or a chunk's page ids, of which a bucket fills and
    reads its first `group_pages(rows)`. Every length is `rows` times a
    constant plus a constant, which is how a program finds its bucket
    again from the array's length (`ModelRunner._unpacked`)."""
    one = {"slot": (), "temps": (1,), "topks": (1,), "topps": (1,),
           "step": ()}
    lanes = {"temps": (rows,), "topks": (rows,), "topps": (rows,),
             "step": ()}
    shapes = {
        "prefill": {"tokens": (1, rows), "last_idx": (),
                    "page_ids": (kinds, blocks), **one},
        "chunk": {"tokens": (1, rows), "start": (), "last_idx": (),
                  "page_ids": (kinds, blocks), "table": (kinds, blocks),
                  **one},
        "decode": {"tokens": (rows,), "slots": (rows,),
                   "positions": (rows,), "tables": (kinds, rows, blocks),
                   **lanes},
        "verify": {"tokens": (1, rows), "start": (), "n_draft": (),
                   "block_ids": (kinds, rows), "offsets": (rows,),
                   "table": (kinds, blocks), **lanes},
    }[kind]
    fields, at = {}, 0
    for name, shape in shapes.items():
        fields[name] = (at, shape)
        at += math.prod(shape)
    return at, fields


def unpack(host, fields: dict) -> dict:
    """The fields of a pack by name, each in its shape: of a numpy array
    writable views (a launch fills them where they lie), of a traced one
    its static slices; a float32 by its bits either way (`ndarray.view`,
    `lax.bitcast_convert_type`), so no bit of a value moves."""
    out = {}
    for name, (at, shape) in fields.items():
        x = host[at:at + math.prod(shape)]
        if name in PACK_FLOATS:
            x = x.view(np.float32) if isinstance(x, np.ndarray) \
                else jax.lax.bitcast_convert_type(x, jnp.float32)
        out[name] = x.reshape(shape)
    return out


def _by_kind(x) -> tuple:
    """A program argument or result a kind of KV layer: a family with
    one kind may give and take it bare."""
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _ordered_bits(x):
    """f32 -> u32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(b >> 31 != 0, ~b, b | jnp.uint32(0x80000000))


def _from_ordered_bits(u):
    b = jnp.where(u >> 31 != 0, u ^ jnp.uint32(0x80000000), ~u)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def truncation_cutoff(logits, scale, top_k, top_p):
    """The logit below which top-k / top-p sampling drops a token.
    logits (S, V) f32; scale (S, 1) temperature; top_k (S,) i32, 0
    disables; top_p (S,) f32, 1.0 disables. Returns (S, 1) f32.

    Found without sorting. Keeping the k best is "fewer than k logits
    lie above the cutoff"; keeping the nucleus is "the probability mass
    above the cutoff is below top_p" (the item that crosses the
    threshold stays in). Both hold from some value upward, so the
    cutoff is the smallest value where both hold: 32 steps of bisection
    over the float's ordered bit pattern, each one masked count and one
    masked sum over the vocabulary. (A full-vocabulary sort costs ~20 s
    of XLA:TPU compile time in every bucketed program that inlines the
    sampler; tests keep the sort as the reference.)"""
    S, V = logits.shape
    probs = jax.nn.softmax(logits / scale, axis=-1)
    keys = _ordered_bits(logits)  # (S, V) u32, ordered like logits
    k = jnp.clip(jnp.where(top_k > 0, top_k, V), 1, V)[:, None]
    p = top_p[:, None]

    def holds(t):  # t (S, 1) u32
        above = keys > t
        n = jnp.sum(above, axis=-1, keepdims=True)
        mass = jnp.sum(jnp.where(above, probs, 0.0), axis=-1,
                       keepdims=True)
        return (n < k) & ((mass < p) | (p >= 1.0))

    def grow(i, below):
        # `below`: the largest pattern found so far at which the
        # condition fails, grown one bit at a time from the top
        bit = jax.lax.shift_left(jnp.uint32(1),
                                 (31 - i).astype(jnp.uint32))
        return jnp.where(holds(below | bit), below, below | bit)

    below = jax.lax.fori_loop(0, 32, grow,
                              jnp.zeros((S, 1), jnp.uint32))
    return _from_ordered_bits(below + 1)


def chunk_rows(prefill_chunk_size: int, block_size: int,
               max_model_len: int) -> int:
    """The rows of a prefill chunk: offsets and chunks must stay
    page-aligned, so the size rounds up to whole pages (and never exceeds
    `max_model_len`)."""
    c = max(block_size, prefill_chunk_size)
    return min(-(-c // block_size) * block_size, max_model_len)


def _next_pow2(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def logprob_at(logits, token: int, temperature: float,
               vocab_size: int) -> float:
    """Log-prob of `token` under the distribution it was sampled from:
    log-softmax over the real vocab (padding masked) of `logits`
    (one position's row), scaled by temperature when temperature > 0
    (greedy reports the unscaled policy log-prob). Host-side float64.

    This is THE logprob definition of the RL determinism contract
    (RL.md): the engine records rollout logprobs with it and the GRPO
    learner's teacher-forced reference recomputes them with it — one
    implementation, so the two cannot drift."""
    x = np.asarray(logits, np.float64)[:vocab_size]
    if temperature > 0:
        x = x / temperature
    x = x - x.max()
    return float(x[int(token)] - np.log(np.exp(x).sum()))


class ModelRunner:
    """Executes prefill/decode for one model instance. Not thread-safe:
    exactly one step-loop thread drives it (the engine enforces this);
    construction may happen on a different thread than stepping."""

    def __init__(
        self,
        adapter: ModelAdapter,
        cfg: Any,
        params: Any,
        *,
        block_size: int,
        num_blocks: "int | Sequence[int]",
        max_model_len: int,
        max_batch_size: int,
        prefill_bucket_min: int = 16,
        prefill_chunk_size: int | None = None,
        mesh=None,
        sample_seed: int = 0,
        num_draft_tokens: int = 0,
    ):
        self.adapter = adapter
        self.cfg = cfg
        self.mesh = mesh
        self.block_size = block_size
        self.max_model_len = max_model_len
        self.max_batch_size = max_batch_size
        self.prefill_bucket_min = prefill_bucket_min
        # chunked prefill: offsets/chunks must stay page-aligned, so the
        # chunk size rounds up to a block multiple (and never exceeds
        # max_model_len). None disables chunking (monolithic prefill).
        if prefill_chunk_size is not None:
            prefill_chunk_size = chunk_rows(prefill_chunk_size, block_size,
                                            max_model_len)
        self.prefill_chunk_size = prefill_chunk_size
        self.max_blocks_per_seq = (
            max_model_len + block_size - 1) // block_size
        # speculative verify: ONE program of static width K+1 (row 0 is
        # the last committed token, rows 1..K the drafts) serves every
        # accept/reject outcome — `n_draft` and `start` are traced
        self.num_draft_tokens = num_draft_tokens
        self.spec_width = num_draft_tokens + 1 if num_draft_tokens else 0

        # a layout, a pool pair and a block table a kind of KV layer;
        # `num_blocks` an int: the first kind's pool, a window kind's
        # sized off the lanes (cache.blocks_by_kind)
        kinds = adapter.kv_kinds(cfg)
        self.num_blocks = blocks_by_kind(
            kinds, num_blocks, block_size,
            self.prefill_chunk_size or max_model_len, max_batch_size)
        self.layouts = tuple(KVLayout.of(kind, n, block_size)
                             for kind, n in zip(kinds, self.num_blocks))
        self.kv_names = tuple(kind.name for kind in kinds)
        # a lane slot's recurrent state, for a family that has it
        self.state_layout = state_layout_of(adapter, cfg, max_batch_size)
        # pages are mutated functionally; serialize compute just in case
        # a stats probe races the step loop
        self._jit_lock = threading.Lock()
        # the last install, for `engine.stats()["weights"]`
        self.weights = {"resident_bytes": 0, "cast_leaves": 0,
                        "installs": 0}
        self._install(params, adapter.resident_fn(params, cfg))
        # K pools and V pools, one of each a kind
        self.k_pages, self.v_pages = zip(*(
            lay.zeros(cfg.dtype, mesh) for lay in self.layouts))
        # {} for a family without recurrent state: the programs take and
        # return it all the same, and compile to what they were
        self.state = (self.state_layout.zeros(mesh)
                      if self.state_layout is not None else {})
        # the last sampled id of every lane slot (see the module's head)
        self.slot_tokens = jnp.zeros(
            (max_batch_size,), jnp.int32,
            device=(NamedSharding(mesh, PartitionSpec())
                    if mesh is not None else None))

        self._base_key = jax.random.PRNGKey(sample_seed)
        self._step_counter = 0
        # donation elides the pages copy per step; CPU jax would only
        # warn "donation is not implemented", so gate on backend
        donate = (1, 2, 4) if jax.default_backend() == "tpu" else ()
        self._prefill_jit = jax.jit(self._prefill_impl, donate_argnums=donate)
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=donate)
        self._chunk_jit = jax.jit(self._chunk_impl, donate_argnums=donate)
        self._verify_jit = jax.jit(self._verify_impl,
                                   donate_argnums=donate[:2])
        self.phases = tracing.PhaseClock("llm.", STEP_PHASES)
        # bytes of device results copied to the host (tokens and logits,
        # every `np.asarray` / `int()` of a program's output) since the
        # engine last took them for the step it was writing down
        self.fetched_bytes = 0
        # the jitted call alone, by kind of program (a prompt's and a
        # chunk's are `prefill`): calls, their wall seconds (the
        # `dispatch` phase less them is the wrapper: the cache probe,
        # the mesh context, the wait for `_jit_lock`, `_note_compile`),
        # and the host arrays a call handed the runtime beside the
        # resident trees, each its own transfer (one a call: its pack),
        # with their bytes
        self.launch = {kind: {"calls": 0, "wall_s": 0.0, "host_arrays": 0,
                              "host_bytes": 0} for kind in CONTEXT_KINDS}
        # a fetch by kind of program: `wait_s` until the first result (the
        # sampled ids, a few bytes) is on the host, so the wait for the
        # program; `copy_s` the logits rows and a routed family's pairs
        # after it; `order_s` a decode step's rows put back in the
        # caller's order (a second copy of them where the orders differ)
        self.fetch = {kind: {"wait_s": 0.0, "copy_s": 0.0, "order_s": 0.0}
                      for kind in CONTEXT_KINDS}
        # leaves every call hands over already on the device, beside the
        # parameters' (`_install` counts those)
        self._carried_leaves = len(jax.tree.leaves(
            (self.k_pages, self.v_pages, self.slot_tokens, self.state)))
        self.expert_pairs: list[np.ndarray] = []
        counts = CONTEXT_COUNTS + SELECT_COUNTS * any(
            lay.select is not None for lay in self.layouts) \
            + DENSE_LATENT_COUNTS * any(
                lay.latent and lay.select is None for lay in self.layouts)
        self.context_slots = {kind: dict.fromkeys(counts, 0)
                              for kind in CONTEXT_KINDS}
        # the same a kind of KV layer
        # and the launches whose read of the kind ran the kernel
        # (`_by_kernel`: which kinds' does, by a program's rows a lane)
        self._by_kernel: dict[int, list[bool]] = {}
        self.context_by_kind = {
            name: {kind: dict.fromkeys(counts + ("kernel_steps",), 0)
                   for kind in CONTEXT_KINDS} for name in self.kv_names}
        # valid rows of K (and as many of V) stored by kind of KV layer
        # and by path: a page at a time (`KVLayout.write_pages` on whole
        # pages: a prompt's and a chunk's programs) or row by row (decode,
        # verify, a bucket that is not whole pages)
        self.rows_written = {name: {"paged": 0, "rowwise": 0}
                             for name in self.kv_names}
        # compile observability: warmup() should account for ALL misses;
        # a mid-stream miss afterwards is the recompile bug these catch
        from ray_tpu.util.metrics import Counter, Histogram

        self._m_compile_miss = Counter(
            "serve_llm_compile_misses_total",
            "Prefill/decode calls that triggered an XLA compile",
            tag_keys=("model", "kind"))
        self._m_compile_s = Histogram(
            "serve_llm_compile_seconds", "XLA compile time per program",
            boundaries=(0.1, 0.5, 1, 5, 10, 30, 60, 120),
            tag_keys=("model", "kind"))

    def _note_compile(self, kind: str, jit_fn, before: int, dt: float):
        tracing.note_compile_if_grew(
            jit_fn, before, dt, self._m_compile_miss, self._m_compile_s,
            f"llm.compile.{kind}",
            tags={"model": self.adapter.name, "kind": kind})

    # ------------------------------------------------------------- traced

    def _sample(self, logits, temps, topks, topps, step):
        """Greedy when temp==0, else temperature sampling with optional
        top-k / top-p (nucleus) truncation; vocab padding is always
        masked out. topks (S,) i32, 0 disables; topps (S,) f32, 1.0
        disables. All in-jit: the truncation cutoff sits behind a
        lax.cond, so a batch with no truncating lane (greedy serving
        traffic, the common case) never pays for it at runtime, without
        a second compiled program variant per bucket (see
        `truncation_cutoff`)."""
        V = logits.shape[-1]
        mask = jnp.arange(V) < self.cfg.vocab_size
        logits = jnp.where(mask, logits, -1e30)
        greedy = jnp.argmax(logits, axis=-1)
        safe = jnp.where(temps > 0, temps, 1.0)[:, None]

        def no_cut(ops):
            return jnp.full((ops[0].shape[0], 1), -jnp.inf,
                            ops[0].dtype)

        cut = jax.lax.cond(jnp.any((topks > 0) | (topps < 1.0)),
                           lambda ops: truncation_cutoff(*ops), no_cut,
                           (logits, safe, topks, topps))
        logits = jnp.where(logits < cut, -jnp.inf, logits)
        key = jax.random.fold_in(self._base_key, step)
        sampled = jax.random.categorical(key, logits / safe, axis=-1)
        return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)

    @staticmethod
    def lanes_per_group(rows: int) -> int:
        """Lanes that share one bound on their context read, in a decode
        program of `rows` lanes: each lane its own up to 4 rows, then 2,
        and 8 groups from 16 rows on. A group costs every layer one loop
        (about 5 us on the v5e, run or not), a wider group reads its short
        lanes to its longest: at 8 rows of gpt2-large and tiles of 128
        slots, 2 a group is 0.7 ms of 5.1 under 1 a group where all lanes
        are short and 0.1 ms over it where their lengths are spread; at 16
        rows of OLMoE 2 and 4 are even and 1 is 0.2 ms behind (PERF.md,
        PR 33)."""
        return 1 if rows < 8 else max(2, rows // 8)

    def _note_context(self, kind: str, lengths, group: int = 1,
                      rows: int = 1, real: int = 0) -> None:
        """Count what a program of `rows` rows a lane launched on lanes of
        `lengths` (as the program has them: ordered, padded) reads of
        their context, in every kind of KV layer, by the path the program
        takes there (`reads_by_kernel`): the kernel's whole pages to each
        lane's own length, and a launch counted in `kernel_steps`, or the
        loops' whole tiles to each group's longest lane. `real`: the rows
        that are not padding (a decode step's lanes, of one row each; the
        one lane's rows of a chunk)."""
        reach = int(np.sum(lengths))
        full = np.size(lengths) * self.max_blocks_per_seq * self.block_size
        total = self.context_slots[kind]
        if rows not in self._by_kernel:
            with self._mesh_ctx():  # the mesh the program is traced under
                self._by_kernel[rows] = [
                    context_attention.reads_by_kernel(lay, rows)
                    for lay in self.layouts]
        for name, lay, kernel in zip(self.kv_names, self.layouts,
                                     self._by_kernel[rows]):
            scored = selected = 0
            if kernel:
                read = int(np.sum(-(-np.asarray(lengths) // self.block_size))
                           * self.block_size)
                valid = reach
                self.context_by_kind[name][kind]["kernel_steps"] += 1
            elif lay.window is None:
                tile = lay.tile_pages * self.block_size
                longest = np.max(np.reshape(lengths, (-1, group)), axis=1)
                read = int(np.sum(-(-longest // tile)) * tile * group)
                valid = reach
                if lay.select is not None:
                    scored = read
                    selected = int(np.sum(np.minimum(lengths, lay.select)))
            else:
                read = np.size(lengths) * lay.window_pages * self.block_size
                valid = int(np.sum(np.minimum(lengths, lay.window - 1)))
            by = self.context_by_kind[name][kind]
            found = {"slots_read": read, "slots_valid": valid,
                     "slots_reach": reach, "slots_full": full,
                     "slots_scored": scored, "slots_selected": selected,
                     "rows": real,
                     "row_slots": reach * (real if rows > 1 else 1)}
            for what in total:
                total[what] += found[what]
                by[what] += found[what]

    @staticmethod
    def _keep_sampled(slot_tokens, slots, nxt):
        """`slot_tokens` with the ids `nxt` left at `slots` (both scalars,
        or both (S,)). A negative slot (a padded lane, a caller that
        holds none) leaves nothing."""
        out_of_range = slot_tokens.shape[0]
        return slot_tokens.at[
            jnp.where(slots >= 0, slots, out_of_range)].set(nxt, mode="drop")

    def _forward(self, fn, state, slots, *args, fresh=None, **extra):
        """A family's forward on `args` -> (what it returns, the lanes'
        state buffers after it). A family with recurrent state also gets
        `state=`, the view its layers read and write the buffers through
        at `slots`, and `extra`; any other is called as it always was."""
        if self.state_layout is None:
            return fn(*args), state
        view = StateView(self.state_layout, state, slots, fresh)
        return fn(*args, state=view, **extra), view.buffers

    def _context(self, k_pages, v_pages, tables, lengths, group: int = 1):
        """The lanes' cached context as a family's forward takes it: a
        `CachedContext`, or one a kind for several kinds of KV layer."""
        ctx = tuple(CachedContext.of(lay, kp, vp, t, lengths, group)
                    for lay, kp, vp, t in zip(
                        self.layouts, _by_kind(k_pages), _by_kind(v_pages),
                        tables))
        return ctx[0] if len(ctx) == 1 else ctx

    def _store(self, k_pages, v_pages, ids, k, v, store):
        """``store(layout, pool, ids of the kind, rows of the kind)`` for
        the K and the V pool of every kind of KV layer. Returns (K pools,
        V pools), a tuple each."""
        pools = [(store(lay, kp, i, kr), store(lay, vp, i, vr))
                 for lay, kp, vp, i, kr, vr in zip(
                     self.layouts, _by_kind(k_pages), _by_kind(v_pages),
                     _by_kind(ids), _by_kind(k), _by_kind(v))]
        return tuple(p[0] for p in pools), tuple(p[1] for p in pools)

    def _write(self, k_pages, v_pages, block_ids, offsets, k, v,
               lane: int | None = None):
        """The rows a forward returned, k and v (layers of the kind, [1,]
        N, HK, width) a kind, stored row by row in their kind's pools at
        the slots ``(block_ids, offsets)`` of the kind's table: a decode
        step's one row a lane, a verify dispatch's rows from a frontier
        that is on no page's edge (`lane` 0: its one lane)."""
        pick = (lambda a: a) if lane is None else (lambda a: a[:, lane])
        return self._store(
            k_pages, v_pages, block_ids, k, v,
            lambda lay, pool, ids, rows: lay.write(pool, ids, offsets,
                                                   pick(rows)))

    def _write_pages(self, k_pages, v_pages, page_ids, k, v):
        """The rows of a prompt's or a chunk's one lane, k and v (layers
        of the kind, 1, Tb, HK, width) a kind, which start on a page's
        edge, stored in their kind's pools a page at a time: group g of
        `block_size` rows in page ``page_ids[g]`` of the kind
        (`KVLayout.write_pages`)."""
        return self._store(
            k_pages, v_pages, page_ids, k, v,
            lambda lay, pool, ids, rows: lay.write_pages(pool, ids,
                                                         rows[:, 0]))

    def _prefill_impl(self, params, k_pages, v_pages, slot_tokens, state,
                      host):
        """host: the launch's pack (`pack_layout`). tokens (1, Tb);
        page_ids (pages of Tb rows,) the page of each
        group of `block_size` positions (the null page 0 for a group that
        is all padding; the padded rows of the last valid page land in
        that page, behind the sequence's frontier). A sequence's first
        rows: its slot's recurrent state starts from zero. Here and in
        the programs below, the pools, the page or block ids and the
        tables are one a kind of KV layer (a tuple; bare for one kind)."""
        f = self._unpacked("prefill", host)
        tokens, last_idx, slot = f["tokens"], f["last_idx"], f["slot"]
        pages = self.layouts[0].group_pages(tokens.shape[1])
        page_ids = tuple(ids[:pages] for ids in f["page_ids"])
        (logits, k, v, *aux), state = self._forward(
            self.adapter.prefill_fn, state, slot, params, tokens, self.cfg,
            fresh=True, n_valid=last_idx + 1)
        k_pages, v_pages = self._write_pages(k_pages, v_pages, page_ids,
                                             k, v)
        last = jnp.take(logits[0], last_idx, axis=0)  # (Vp,)
        nxt = self._sample(last[None, :], f["temps"], f["topks"],
                           f["topps"], f["step"])[0]
        slot_tokens = self._keep_sampled(slot_tokens, slot, nxt)
        return nxt, last, k_pages, v_pages, slot_tokens, state, tuple(aux)

    def _chunk_impl(self, params, k_pages, v_pages, slot_tokens, state,
                    host):
        """Prefill a chunk of ONE sequence from a position offset.

        host: the launch's pack (`pack_layout`). tokens (1, Tb) at
        absolute positions start..start+Tb-1, `start`
        on a page's edge; table (maxB,) is the sequence's full block
        table, read for context (positions < start); page_ids as in
        `_prefill_impl`, the pages from `start` on. `start` is
        traced, so one compiled program per chunk-length bucket serves
        every offset. Recurrent state is carried chunk to chunk in the
        lane's slot, from zero where `start` is 0."""
        f = self._unpacked("chunk", host)
        tokens, start, last_idx, slot = (f["tokens"], f["start"],
                                         f["last_idx"], f["slot"])
        Tb = tokens.shape[1]
        pages = self.layouts[0].group_pages(Tb)
        page_ids = tuple(ids[:pages] for ids in f["page_ids"])
        table = tuple(f["table"])
        chunk_mask = (jnp.arange(Tb)[None, :] <= last_idx)  # (1, Tb)
        (logits, k, v, *aux), state = self._forward(
            self.adapter.chunk_fn, state, slot, params, tokens, start,
            self._context(k_pages, v_pages,
                          [t[None] for t in _by_kind(table)], start[None]),
            chunk_mask, self.cfg, fresh=start == 0, n_valid=last_idx + 1)
        k_pages, v_pages = self._write_pages(k_pages, v_pages, page_ids,
                                             k, v)
        last = jnp.take(logits[0], last_idx, axis=0)  # (Vp,)
        nxt = self._sample(last[None, :], f["temps"], f["topks"],
                           f["topps"], f["step"])[0]
        slot_tokens = self._keep_sampled(slot_tokens, slot, nxt)
        return nxt, last, k_pages, v_pages, slot_tokens, state, tuple(aux)

    def _verify_impl(self, params, k_pages, v_pages, host):
        """Score a drafted run of ONE sequence in one dispatch and
        accept/reject in-jit (no logits round-trip to host).

        host: the launch's pack (`pack_layout`). tokens (1, W) with
        W = num_draft_tokens + 1: row 0 is the last
        committed token at traced position `start` (== pos - 1), rows
        1..n_draft the proposer's guesses at start+1.., padded tail to
        the static width. The program is the `prefill_chunk` shape —
        context gathered through `table` for positions < start, causal
        mask within the window — but samples EVERY window position and
        applies the acceptance rule: keep drafts while draft[j] equals
        the token the model itself samples at that position, then emit
        the model's own correction token at the first mismatch (or the
        bonus token after a full accept). K/V is scattered for all
        window positions; slots past the accepted frontier are garbage
        that stays masked (ctx covers only positions < start') and is
        overwritten as the frontier advances — rollback is frontier
        arithmetic, not data movement.

        Returns (emitted (W,), n_acc scalar, logits (W, Vp), pages, the
        forward's extras): the caller commits emitted[:n_acc + 1]."""
        f = self._unpacked("verify", host)
        tokens, start, n_draft = f["tokens"], f["start"], f["n_draft"]
        block_ids, table = tuple(f["block_ids"]), tuple(f["table"])
        W = tokens.shape[1]
        chunk_mask = (jnp.arange(W)[None, :] <= n_draft)  # (1, W)
        logits, k, v, *aux = self.adapter.chunk_fn(
            params, tokens, start,
            self._context(k_pages, v_pages,
                          [t[None] for t in _by_kind(table)], start[None]),
            chunk_mask, self.cfg)
        k_pages, v_pages = self._write(k_pages, v_pages, block_ids,
                                       f["offsets"], k, v, lane=0)
        lg = logits[0]  # (W, Vp)
        target = self._sample(lg, f["temps"], f["topks"], f["topps"],
                              f["step"])  # (W,)
        # target[j] is the model's own token FOR position start+j+1;
        # accept drafts while they match it, longest-prefix semantics
        match = (target[:-1] == tokens[0, 1:]) \
            & (jnp.arange(W - 1) < n_draft)
        n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32)))
        emitted = jnp.where(jnp.arange(W) <= n_acc, target, -1)
        return emitted, n_acc, lg, k_pages, v_pages, tuple(aux)

    def _decode_impl(self, params, k_pages, v_pages, slot_tokens, state,
                     host):
        """host: the launch's pack (`pack_layout`).
        tokens/slots/positions/temps (Sb,); tables (Sb,
        max_blocks_per_seq). Run the model's decode step, each layer
        reading its lanes' context through the tables: with the kernel to
        each lane's own length where `reads_by_kernel` allows, else as far
        as the longest lane of each group of `lanes_per_group` rows
        reaches (the caller orders the rows by position); scatter the new
        K/V at each lane's position, sample. A lane whose token is -1
        feeds the id an earlier
        program left at its slot. Recurrent state moves one step in the
        slots of the step's lanes; a padded lane (slot -1) and a slot no
        lane owns keep theirs."""
        f = self._unpacked("decode", host)
        tokens, slots, positions = f["tokens"], f["slots"], f["positions"]
        tables = tuple(f["tables"])
        Bs = self.block_size
        tokens = jnp.where(tokens >= 0, tokens,
                           slot_tokens[jnp.maximum(slots, 0)])
        (logits, k_new, v_new, *aux), state = self._forward(
            self.adapter.decode_fn, state, slots, params, tokens, positions,
            self._context(k_pages, v_pages, _by_kind(tables), positions,
                          self.lanes_per_group(tokens.shape[0])),
            self.cfg)
        block_ids = tuple(jnp.take_along_axis(
            t, (positions // Bs)[:, None], axis=1)[:, 0]
            for t in _by_kind(tables))
        offsets = positions % Bs
        k_pages, v_pages = self._write(k_pages, v_pages, block_ids, offsets,
                                       k_new, v_new)
        nxt = self._sample(logits, f["temps"], f["topks"], f["topps"],
                           f["step"])
        slot_tokens = self._keep_sampled(slot_tokens, slots, nxt)
        return nxt, logits, k_pages, v_pages, slot_tokens, state, tuple(aux)

    # -------------------------------------------------------------- host

    def _fetch(self, kind: str, *results, aux=()) -> list[np.ndarray]:
        """Device results as numpy arrays: the wait for the program that
        makes them (until the first of them, the sampled ids, is on the
        host), then the copy of the rest, written down apart by `kind`
        of program (`self.fetch`). `aux` is what the family's
        forward returned beside (logits, k, v): nothing, or the routed
        experts' pairs per layer and expert, kept for the engine
        (`take_expert_pairs`)."""
        t0 = time.perf_counter()
        out = [np.asarray(results[0])]
        t1 = time.perf_counter()
        out += [np.asarray(r) for r in results[1:]]
        self.fetched_bytes += sum(a.nbytes for a in out)
        if aux:  # routed experts only
            extra = [np.asarray(r) for r in aux]
            self.fetched_bytes += sum(a.nbytes for a in extra)
            self.expert_pairs.extend(extra)
        spent = self.fetch[kind]
        spent["wait_s"] += t1 - t0
        spent["copy_s"] += time.perf_counter() - t1
        return out

    def collect(self, launched: Launched) -> tuple:
        """Wait for a launched program and read its results: (sampled
        id, its logits row) of a prefill or a chunk, (ids, logits rows)
        of a decode's real lanes, in the order the caller gave them."""
        with self.phases.phase("fetch"):
            nxt, logits = self._fetch(launched.kind, *launched.results,
                                      aux=launched.aux)
            if launched.order is None:
                return int(nxt), logits
            # back to the caller's order: its lane i ran as row
            # `row_of[i]` (a view of the rows, not a copy, where the two
            # orders agree; where they differ `logits[row_of]` is a
            # gather, so the rows `_fetch` copied are copied a second
            # time: 1.6 ms of a 64-lane step, PERF.md §5. A caller that
            # took the rows by index would not need it)
            t0 = time.perf_counter()
            row_of = np.argsort(launched.order)
            if np.array_equal(row_of, np.arange(len(row_of))):
                row_of = slice(len(row_of))
            out = [int(t) for t in nxt[row_of]], logits[row_of]
            self.fetch[launched.kind]["order_s"] += time.perf_counter() - t0
            return out

    def take_expert_pairs(self) -> list[np.ndarray]:
        """The (L, n_experts) pairs-per-expert arrays of the programs run
        since the last call, padded rows included (the device computed
        them); always empty for a dense model."""
        taken, self.expert_pairs = self.expert_pairs, []
        return taken

    def _mesh_ctx(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _note_launch(self, kind: str, wall: float, host: tuple) -> None:
        """One jitted call into `self.launch[kind]`: its wall seconds
        (read inside `_jit_lock`) and the `host` arrays of the step it
        was handed, counted as they are in hand."""
        spent = self.launch[kind]
        spent["calls"] += 1
        spent["wall_s"] += wall
        spent["host_arrays"] += len(host)
        spent["host_bytes"] += sum(a.nbytes for a in host)

    def _layout(self, kind: str, rows: int) -> tuple[int, dict]:
        """`pack_layout` of a `kind` program of `rows` at this runner's
        shapes."""
        return pack_layout(kind, rows, len(self.layouts),
                           self.max_blocks_per_seq)

    def _pack(self, kind: str, rows: int, **values
              ) -> tuple[np.ndarray, dict]:
        """A launch's pack (`pack_layout`) and its fields as views to fill
        (`unpack`), all zeros as it comes (token 0, the null page, greedy)
        but for `values`, each written to every element of its field.
        A fresh array every launch, written by nobody once the jitted call
        has it: the runtime reads a host buffer when it pleases (the CPU
        backend may not copy it at all), and with a step in flight the
        next launch is prepared before this one has run."""
        size, layout = self._layout(kind, rows)
        host = np.zeros((size,), np.int32)
        fields = unpack(host, layout)
        for name, value in values.items():
            fields[name][...] = value
        return host, fields

    def _unpacked(self, kind: str, host) -> dict:
        """The fields of the pack a `kind` program is traced on, its
        bucket found again from the array's length (`pack_layout`)."""
        base, one = (self._layout(kind, rows)[0] for rows in (0, 1))
        rows = (host.shape[0] - base) // (one - base)
        return unpack(host, self._layout(kind, rows)[1])

    @property
    def resident_leaves(self) -> int:
        """Arrays every call hands the runtime already on the device: the
        leaves of the parameters, the pools, the lane slots' last ids and
        the lanes' state."""
        return self._param_leaves + self._carried_leaves

    @functools.cached_property
    def state_by_kernel(self) -> bool:
        """Whether the decode programs step the lanes' SSM state with the
        Pallas kernel (`ssm_step.steps_by_kernel`, asked under the mesh
        the programs are traced under): what `StateSlots` counts."""
        with self._mesh_ctx():
            return self.state_layout is not None \
                and ssm_step.steps_by_kernel(self.state_layout)

    def prefill_bucket(self, n: int) -> int:
        if n > self.max_model_len:
            raise ValueError(
                f"prompt of {n} tokens exceeds max_model_len "
                f"{self.max_model_len}")
        return min(_next_pow2(n, self.prefill_bucket_min),
                   self.max_model_len)

    def decode_bucket(self, n: int) -> int:
        return min(_next_pow2(n, 1), self.max_batch_size)

    def chunk_bucket(self, n: int) -> int:
        cap = self.prefill_chunk_size or self.max_model_len
        if n > cap:
            raise ValueError(f"chunk of {n} tokens exceeds chunk size {cap}")
        return min(_next_pow2(n, self.prefill_bucket_min), cap)

    def _lists(self, table) -> Sequence:
        """A lane's page lists, one a kind of KV layer: `table` as it is
        where it gives one a kind, or one flat list taken for every kind
        (the one kind's; the null table of warm-up)."""
        flat = len(table) == 0 or np.ndim(table[0]) == 0
        return [table] * len(self.layouts) if flat else table

    def _tables(self, table, out: np.ndarray) -> np.ndarray:
        """A lane's block tables as the programs take them, one a kind,
        written into `out` (kinds, max_blocks_per_seq), zeros as it comes:
        the null page behind a table's end."""
        for tab, t in zip(out, self._lists(table)):
            tab[:len(t)] = t
        return out

    def _page_ids(self, table, start: int, n: int, width: int,
                  out: np.ndarray) -> None:
        """The pages of a program's `width` rows from position `start` (on
        a page's edge), one id a group of `block_size` rows and kind of KV
        layer, written into `out` (kinds, max_blocks_per_seq), zeros as it
        comes: the lane's page for a group that holds one of the `n`
        valid rows, the null page for a group that is all padding (and
        behind the `group_pages(width)` ids the program reads). The rows
        are counted as written, by the path the program takes."""
        first = start // self.block_size
        for lay, t, ids, written in zip(self.layouts, self._lists(table),
                                        out, self.rows_written.values()):
            pages = t[first:first + lay.group_pages(n)]
            ids[:len(pages)] = pages
            written["paged" if lay.whole_pages(width) else "rowwise"] += n

    def launch_prefill(self, token_ids: Sequence[int],
                       table: Sequence, temperature: float,
                       top_k: int = 0, top_p: float = 1.0,
                       slot: int = -1) -> Launched:
        """Enqueue one prompt's monolithic prefill. `table` must cover
        blocks_for_tokens(len(token_ids)) pages; the sampled id is also
        left at `slot` of `slot_tokens`."""
        with self.phases.phase("prepare"):
            n = len(token_ids)
            Tb = self.prefill_bucket(n)
            host, f = self._pack("prefill", Tb, last_idx=n - 1, slot=slot,
                                 temps=temperature, topks=top_k, topps=top_p)
            f["tokens"][0, :n] = token_ids
            self._page_ids(table, 0, n, Tb, f["page_ids"])
            self._step_counter += 1
            f["step"][...] = self._step_counter
        with self.phases.phase("dispatch"):
            before = tracing.jit_cache_size(self._prefill_jit)
            t0 = time.perf_counter()
            with self._mesh_ctx(), self._jit_lock:
                w0 = time.perf_counter()
                (nxt, last, self.k_pages, self.v_pages, self.slot_tokens,
                 self.state, aux) = self._prefill_jit(
                    self.params, self.k_pages, self.v_pages,
                    self.slot_tokens, self.state, host)
                wall = time.perf_counter() - w0
            self._note_compile("prefill", self._prefill_jit, before,
                               time.perf_counter() - t0)
            self._note_launch("prefill", wall, (host,))
        return Launched("prefill", (nxt, last), aux, None)

    def prefill(self, token_ids: Sequence[int], table: Sequence,
                temperature: float, top_k: int = 0, top_p: float = 1.0
                ) -> tuple[int, np.ndarray]:
        """Run one prompt through monolithic prefill; returns (first
        generated token, last-position logits)."""
        return self.collect(self.launch_prefill(
            token_ids, table, temperature, top_k, top_p))

    def launch_chunk(self, token_ids: Sequence[int], start: int,
                     table: Sequence, temperature: float,
                     top_k: int = 0, top_p: float = 1.0,
                     slot: int = -1) -> Launched:
        """Enqueue a prefill-from-offset: `token_ids` (<=
        prefill_chunk_size) at absolute positions start..start+n-1
        against the cached context in `table` (which must already hold
        valid KV for every position < start, and own the pages the chunk
        writes). `start` must be page-aligned. The caller only uses the
        results (sampled next token, last-chunk-position logits) of the
        final chunk."""
        with self.phases.phase("prepare"):
            n = len(token_ids)
            if start % self.block_size:
                raise ValueError(
                    f"chunk start {start} not page-aligned "
                    f"(block_size={self.block_size})")
            Tb = self.chunk_bucket(n)
            host, f = self._pack("chunk", Tb, start=start, last_idx=n - 1,
                                 slot=slot, temps=temperature, topks=top_k,
                                 topps=top_p)
            f["tokens"][0, :n] = token_ids
            self._tables(table, f["table"])
            self._page_ids(table, start, n, Tb, f["page_ids"])
            self._note_context("prefill", [start], rows=Tb, real=n)
            self._step_counter += 1
            f["step"][...] = self._step_counter
        with self.phases.phase("dispatch"):
            before = tracing.jit_cache_size(self._chunk_jit)
            t0 = time.perf_counter()
            with self._mesh_ctx(), self._jit_lock:
                w0 = time.perf_counter()
                (nxt, last, self.k_pages, self.v_pages, self.slot_tokens,
                 self.state, aux) = self._chunk_jit(
                    self.params, self.k_pages, self.v_pages,
                    self.slot_tokens, self.state, host)
                wall = time.perf_counter() - w0
            self._note_compile("prefill_chunk", self._chunk_jit, before,
                               time.perf_counter() - t0)
            self._note_launch("prefill", wall, (host,))
        return Launched("prefill", (nxt, last), aux, None)

    def prefill_chunk(self, token_ids: Sequence[int], start: int,
                      table: Sequence, temperature: float,
                      top_k: int = 0, top_p: float = 1.0
                      ) -> tuple[int, np.ndarray]:
        """`launch_chunk`, then its results: (sampled next token,
        last-chunk-position logits)."""
        return self.collect(self.launch_chunk(
            token_ids, start, table, temperature, top_k, top_p))

    def launch_decode(self, items: Sequence[DecodeItem]) -> Launched:
        """Enqueue one decode step for up to max_batch_size sequences.
        The program's rows are the lanes by position, longest first
        (padded rows, position 0, last), so that consecutive rows read
        about as much context; `collect` undoes the order."""
        with self.phases.phase("prepare"):
            S = len(items)
            if not 0 < S <= self.max_batch_size:
                raise ValueError(f"decode batch of {S}")
            Sb = self.decode_bucket(S)
            order = np.argsort([-it.pos for it in items], kind="stable")
            # a padded lane leaves its id nowhere and truncates nothing
            host, f = self._pack("decode", Sb, slots=-1, topps=1.0)
            toks, slots, poss, tables, temps, topks, topps = (
                f[name] for name in ("tokens", "slots", "positions",
                                     "tables", "temps", "topks", "topps"))
            # one list a kind, or one flat list for every kind: the same
            # form in every item of a step
            first = items[0].table
            flat = len(first) == 0 or np.ndim(first[0]) == 0
            for i, it in enumerate(items[j] for j in order):
                toks[i] = it.token
                slots[i] = it.slot
                poss[i] = it.pos
                for k, rows in enumerate(tables):
                    t = it.table if flat else it.table[k]
                    rows[i, :len(t)] = t
                temps[i] = it.temperature
                topks[i] = it.top_k
                topps[i] = it.top_p
            self._note_context("decode", poss, self.lanes_per_group(Sb),
                               real=S)
            for written in self.rows_written.values():
                written["rowwise"] += S
            self._step_counter += 1
            f["step"][...] = self._step_counter
        with self.phases.phase("dispatch"):
            before = tracing.jit_cache_size(self._decode_jit)
            t0 = time.perf_counter()
            with self._mesh_ctx(), self._jit_lock:
                w0 = time.perf_counter()
                (nxt, logits, self.k_pages, self.v_pages, self.slot_tokens,
                 self.state, aux) = self._decode_jit(
                    self.params, self.k_pages, self.v_pages,
                    self.slot_tokens, self.state, host)
                wall = time.perf_counter() - w0
            self._note_compile("decode", self._decode_jit, before,
                               time.perf_counter() - t0)
            self._note_launch("decode", wall, (host,))
        return Launched("decode", (nxt, logits), aux, order)

    def decode(self, items: Sequence[DecodeItem]
               ) -> tuple[list[int], np.ndarray]:
        """One decode step; returns (next token per item, logits
        (len(items), Vp))."""
        return self.collect(self.launch_decode(items))

    def verify(self, token: int, pos: int, draft: Sequence[int],
               table: Sequence[int], temperature: float,
               top_k: int = 0, top_p: float = 1.0
               ) -> tuple[list[int], np.ndarray]:
        """Verify a drafted run for one sequence: one dispatch scores
        `token` (at position pos, the frontier) plus up to
        num_draft_tokens drafts at pos+1.., accepts the longest matching
        prefix in-jit, and returns (committed tokens, their logits rows).
        len(result[0]) is 1 (all rejected) .. len(draft)+1 (full accept
        plus the bonus token); the KV for every committed token is
        already in the pages when this returns."""
        with self.phases.phase("prepare"):
            if not self.spec_width:
                raise RuntimeError("runner built without num_draft_tokens")
            n_draft = len(draft)
            W = self.spec_width
            if not 0 < n_draft < W:
                raise ValueError(
                    f"draft of {n_draft} tokens (max {W - 1})")
            if pos + n_draft >= self.max_model_len:
                raise ValueError(
                    f"drafted run past max_model_len: pos {pos} + "
                    f"{n_draft} drafts >= {self.max_model_len}")
            host, f = self._pack("verify", W, start=pos, n_draft=n_draft,
                                 temps=temperature, topks=top_k, topps=top_p)
            f["tokens"][0, 0] = token
            f["tokens"][0, 1:1 + n_draft] = draft
            tab = self._tables(table, f["table"])
            positions = pos + np.arange(W)
            # padded tail rows write to the null page at in-range offsets
            f["block_ids"][:, :n_draft + 1] = tab[
                :, positions[:n_draft + 1] // self.block_size]
            f["offsets"][:] = positions % self.block_size
            self._note_context("verify", [pos], rows=W)
            for written in self.rows_written.values():
                written["rowwise"] += n_draft + 1
            self._step_counter += 1
            f["step"][...] = self._step_counter
        with self.phases.phase("dispatch"):
            before = tracing.jit_cache_size(self._verify_jit)
            t0 = time.perf_counter()
            with self._mesh_ctx(), self._jit_lock:
                w0 = time.perf_counter()
                emitted, n_acc, logits, self.k_pages, self.v_pages, aux = \
                    self._verify_jit(
                        self.params, self.k_pages, self.v_pages, host)
                wall = time.perf_counter() - w0
            self._note_compile("verify", self._verify_jit, before,
                               time.perf_counter() - t0)
            self._note_launch("verify", wall, (host,))
        with self.phases.phase("fetch"):
            n_acc, emitted, logits = self._fetch("verify", n_acc, emitted,
                                                 logits, aux=aux)
            n_em = int(n_acc) + 1
            return [int(t) for t in emitted[:n_em]], logits[:n_em]

    def warmup(self) -> int:
        """Compile every (bucket, kind) program up front so no request
        ever pays a mid-stream XLA compile (the TPU serving idiom:
        static shapes, all compiled at startup). All writes/reads target
        the null page, so the warm cache state is untouched as far as
        any real sequence is concerned. Returns #programs compiled.

        With chunked prefill enabled the engine only ever runs
        monolithic prefill on prompts that fit one chunk, so both the
        monolithic and the chunk buckets cap at prefill_chunk_size —
        long prompts always go through the chunk program."""
        null_table = [0] * self.max_blocks_per_seq
        cap = self.prefill_chunk_size or self.max_model_len
        b = min(self.prefill_bucket_min, cap)
        while True:
            self.prefill([1] * b, null_table, 0.0)
            if b >= cap:
                break
            b = min(b * 2, cap)
        if self.prefill_chunk_size is not None:
            b = min(self.prefill_bucket_min, cap)
            while True:
                # start=0 is fine: start is traced, the program is
                # shared across offsets — only Tb shapes the compile
                self.prefill_chunk([1] * b, 0, null_table, 0.0)
                if b >= cap:
                    break
                b = min(b * 2, cap)
        s = 1
        while True:
            self.decode([DecodeItem(1, 0, null_table, 0.0)] * s)
            if s >= self.max_batch_size:
                break
            s = min(s * 2, self.max_batch_size)
        if self.spec_width:
            # single fixed-width program: one warmup call covers every
            # draft length (n_draft is traced)
            self.verify(1, 0, [1], null_table, 0.0)
        return self.compiled_signatures()

    def _install(self, given: Any, resident: Any) -> None:
        """Make `resident`, which is `given` with every leaf in its
        resident dtype, the tree the programs run on, and account for
        it: its bytes, and how many leaves had to be converted."""
        if self.mesh is not None:
            from ray_tpu.parallel.sharding import shard_pytree

            resident = shard_pytree(resident, self.adapter.rules_fn(),
                                    self.mesh)
        leaves = jax.tree.leaves(resident)
        weights = {
            "resident_bytes": sum(int(np.prod(r.shape)) * r.dtype.itemsize
                                  for r in leaves),
            "cast_leaves": sum(
                getattr(g, "dtype", None) != r.dtype
                for g, r in zip(jax.tree.leaves(given), leaves)),
            "installs": self.weights["installs"] + 1}
        with self._jit_lock:
            self.params, self.weights = resident, weights
            self._param_leaves = len(leaves)

    def set_params(self, params: Any) -> None:
        """Install a new parameter pytree (weight hot-swap). The tree
        structure and leaf shapes must match the resident params, and
        leaves are cast to the resident dtypes (a trainer's float32 tree
        is rounded once, here, where the resident leaf is bf16; a leaf
        already in its resident dtype is taken as it is), so a swap can
        NEVER trigger a recompile — the compiled programs see new argument
        values, not new signatures. With a mesh, leaves are re-sharded
        through the same partition rules as construction. The caller
        guarantees no device program is in flight (the engine holds its
        step lock across the swap); `_jit_lock` is still taken so a
        concurrent stats probe cannot observe a half-installed tree."""
        old_struct = jax.tree_util.tree_structure(self.params)
        new_struct = jax.tree_util.tree_structure(params)
        if old_struct != new_struct:
            raise ValueError(
                f"param tree mismatch: engine has {old_struct}, "
                f"update has {new_struct}")

        def cast(new, old):
            arr = jnp.asarray(new, dtype=old.dtype)
            if arr.shape != old.shape:
                raise ValueError(
                    f"param shape mismatch: engine has {old.shape}, "
                    f"update has {arr.shape}")
            return arr

        self._install(params, jax.tree.map(cast, params, self.params))

    def reset_cache(self) -> None:
        """Zero the pages and the lanes' state (tests); allocator state
        lives in BlockPool."""
        self.k_pages = jax.tree.map(jnp.zeros_like, self.k_pages)
        self.v_pages = jax.tree.map(jnp.zeros_like, self.v_pages)
        self.state = jax.tree.map(jnp.zeros_like, self.state)

    def compiled_signatures(self) -> int:
        """Number of distinct compiled programs so far — the
        recompilation-boundedness observable used by tests/metrics.
        Bounded by #length-buckets + #batch-buckets by construction."""
        try:
            return (self._prefill_jit._cache_size()
                    + self._chunk_jit._cache_size()
                    + self._decode_jit._cache_size()
                    + self._verify_jit._cache_size())
        except Exception:  # noqa: BLE001
            return -1
