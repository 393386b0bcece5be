"""Operations and bytes that latent attention under a learned indexer
REQUIRES of a program's read of its lanes' cached context, one layer:

- the **index pass**: the indexer-key row of every cached slot below a
  lane's length, read once, and for every (query row, such slot) pair the
  `index_n_heads` products over `index_head_dim` (their relu and weighted
  sum are not counted: a fifth of a percent of the products);
- the **attention core**: the latent row of every slot a row attends
  after the choice, read once, and for every (query row, attended slot)
  pair every head's score over ``kv_lora_rank + qk_rope_head_dim`` lanes
  and value product over ``kv_lora_rank``, the absorbed form's arithmetic
  (the up-projected form would read no fewer rows).

Slots scored beyond a lane's length (whole tiles, a group's longest lane),
latent rows read beyond the chosen (a chunk folds whole tiles), the
latent row's padding lanes and the program's own rows are the program's
choice and do not count. A chunk's rows are taken to attend the same
slots, the fewest bytes that could serve them: the share of the roofline
that results is a floor, not an estimate.
"""

from __future__ import annotations

from benchmark.flops import least_seconds


def index_flops(pairs: float, heads: int, width: int) -> float:
    return pairs * 2.0 * heads * width


def index_bytes(slots: float, width: int, itemsize: int = 2) -> float:
    return slots * float(width * itemsize)


def core_flops(pairs: float, heads: int, latent: int, rope: int) -> float:
    """q.k over latent + rope lanes and p.v over latent lanes, a head."""
    return pairs * 2.0 * heads * (2 * latent + rope)


def core_bytes(slots: float, latent: int, rope: int,
               itemsize: int = 2) -> float:
    return slots * float((latent + rope) * itemsize)


def program_least_seconds(cfg: dict, rows: int, lanes: int, valid: float,
                          selected: float, device_kind: str
                          ) -> tuple[float, float]:
    """(index pass, attention core): the least seconds the chip could take
    for ONE program of `lanes` lanes of `rows` rows, every layer, where a
    lane has `valid` cached slots and a row attends `selected` of them."""
    layers = cfg["num_hidden_layers"]
    index = least_seconds(
        layers * lanes * index_flops(rows * valid, cfg["index_n_heads"],
                                     cfg["index_head_dim"]),
        layers * lanes * index_bytes(valid, cfg["index_head_dim"]),
        device_kind)[0]
    core = least_seconds(
        layers * lanes * core_flops(rows * selected,
                                    cfg["num_attention_heads"],
                                    cfg["kv_lora_rank"],
                                    cfg["qk_rope_head_dim"]),
        layers * lanes * core_bytes(selected, cfg["kv_lora_rank"],
                                    cfg["qk_rope_head_dim"]),
        device_kind)[0]
    return index, core
