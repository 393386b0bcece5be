"""Resolve the `module:function` names a configuration file gives for its
model family. The harness has no `if model == "gpt2"`: a new family is a
new configuration file naming its own functions."""

from __future__ import annotations

import importlib


def load(spec: str):
    """`"pkg.mod:attr.sub"` -> the object."""
    module, _, attr = spec.partition(":")
    obj = importlib.import_module(module)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


SIZE_KEYS = ("n_embd", "n_layer", "n_head", "n_positions", "vocab_size")


def sizes(config: dict) -> dict:
    """The published sizes of a configuration file, as the readers, the
    flops functions and the references receive them."""
    return {k: config[k] for k in SIZE_KEYS}
