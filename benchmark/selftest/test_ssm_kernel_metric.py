"""CPU test of `ssm_kernel_steps_pct` (PR 49): the reader on synthetic
polls of `engine_stats()`, and on a parent's polls, which lack the
counter. Run by hand with the rest of `benchmark/selftest`."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read(observed):
    path = os.path.join(ROOT, "benchmark", "layer_metrics",
                        "ssm_kernel_steps_pct.py")
    spec = importlib.util.spec_from_file_location("m_ssm_kernel", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(observed)


def _poll(kernel, **steps):
    """`steps`: decode programs launched by their rows ("64": n), as
    `StateSlots.stats()` writes them."""
    state = {"slots": 64, "resets": 3, "decode_lanes": 60 * sum(
        steps.values()), "decode_steps": dict(steps)}
    if kernel is not None:
        state["kernel_steps"] = kernel
    return {"steps": {"decode": sum(steps.values()), "prefill": 7},
            "state": state}


def test_the_share_is_of_the_windows_decode_steps():
    polls = [_poll(100, **{"64": 90, "32": 10}),
             _poll(300, **{"64": 280, "32": 20}),
             _poll(1100, **{"64": 1070, "32": 30})]
    assert _read({"polls": polls}) == pytest.approx(100.0)
    # warm-up and the pre-roll lie before the first poll and do not count
    assert _read({"polls": polls[1:]}) == pytest.approx(100.0)
    # a size the predicate keeps on the jnp form: 0, not None
    off = [_poll(0, **{"32": 100}), _poll(0, **{"32": 200})]
    assert _read({"polls": off}) == 0.0
    # a replica whose programs changed path between the polls (they never
    # do: the share is then that of the launches)
    part = [_poll(0, **{"64": 100}), _poll(50, **{"64": 300})]
    assert _read({"polls": part}) == pytest.approx(25.0)


def test_nothing_to_read_gives_none():
    # the parent: the state's counts without the kernel's
    parent = [_poll(None, **{"64": 100}), _poll(None, **{"64": 200})]
    assert _read({"polls": parent}) is None
    # a family without recurrent state: `state` is {}
    assert _read({"polls": [{"steps": {"decode": 1}, "state": {}},
                            {"steps": {"decode": 2}, "state": {}}]}) is None
    assert _read({"polls": []}) is None
    assert _read({}) is None
    assert _read({"polls": [_poll(5, **{"64": 5})]}) is None
    still = _poll(5, **{"64": 5})
    assert _read({"polls": [still, still]}) is None
