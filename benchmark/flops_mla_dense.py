"""Operations and bytes that latent attention with NO indexer REQUIRES of
a program's read of its lanes' cached context, one layer, whatever form
implements it:

- for every (query row, cached slot below the lane's length) pair, every
  head's score over ``qk_nope_head_dim + qk_rope_head_dim`` lanes and its
  value product over ``v_head_dim``: the per-head (up-projected) form's
  arithmetic, which no form undercuts. The absorbed form the program
  runs multiplies over ``kv_lora_rank + qk_rope_head_dim`` and
  ``kv_lora_rank`` lanes instead, 3.4 times as much at 512 / 64 / 128 /
  128, and shows that as a low share: the finding, not an error;
- the latent row of every cached slot of a lane, read once a program:
  ``kv_lora_rank + qk_rope_head_dim`` lanes (the row's padding lanes are
  the program's choice and do not count).

Slots read beyond a lane's length (whole tiles, a group's longest lane),
the program's own rows and the up-projection of the cached rows that the
per-head form would need are not counted: the share that results is a
floor.
"""

from __future__ import annotations

from benchmark.flops import least_seconds


def read_flops(pairs: float, heads: int, nope: int, rope: int,
               v: int) -> float:
    """q.k over nope + rope lanes and p.v over v lanes, a head."""
    return pairs * 2.0 * heads * (nope + rope + v)


def read_bytes(slots: float, latent: int, rope: int,
               itemsize: int = 2) -> float:
    return slots * float((latent + rope) * itemsize)


def program_least_seconds(cfg: dict, pairs: float, slots: float,
                          device_kind: str) -> tuple[float, str]:
    """(the least seconds the chip could take for the dense read of ONE
    program, every layer, which peak bounds it): `pairs` (row, cached
    slot) pairs and `slots` cached slots a layer."""
    layers = cfg["num_hidden_layers"]
    return least_seconds(
        layers * read_flops(pairs, cfg["num_attention_heads"],
                            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                            cfg["v_head_dim"]),
        layers * read_bytes(slots, cfg["kv_lora_rank"],
                            cfg["qk_rope_head_dim"]),
        device_kind)
