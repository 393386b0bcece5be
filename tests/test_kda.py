"""The KDA mixer's mathematics (`ray_tpu/models/kda.py`) at a tiny size in
float32 on the CPU: the chunked form against the recurrence token by
token (written out here, from the equation), the one-step form, chunks
carried through a `StateView`, and `moe.route`'s group limit.

TOL: the three forms do the same float32 arithmetic in another order;
2e-5 is ten times what they read here on outputs and states of
magnitude 1."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kda, moe
from ray_tpu.models.ling3 import Ling3Config, init_ling3
from ray_tpu.serve.llm.cache import StateLayout, StateView

TOL = 2e-5
H, D = 3, 16


def _token_scan(q, k, v, g, beta, S):
    """S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} + beta k v^T; o_t =
    S_t^T q_t, a row at a time in numpy float64."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, S))
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, :, None] * S
        for h in range(S.shape[0]):
            S[h] = S[h] - beta[t, h] * np.outer(k[t, h], k[t, h] @ S[h]) \
                + beta[t, h] * np.outer(k[t, h], v[t, h])
        out.append(np.einsum("hc,hcv->hv", q[t], S))
    return np.stack(out), S


def _inputs(T, seed=0, floor_block=None):
    """Random rows; `floor_block` = (first, last): g at the lower bound -5
    on a quarter of the channels for every row of [first, last)."""
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(T, H, D)).astype(np.float32)
               for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(D)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    g = (-5 * rng.uniform(size=(T, H, D)) ** 3).astype(np.float32)
    if floor_block:
        g[floor_block[0]:floor_block[1], :, :D // 4] = -5.0
    beta = rng.uniform(size=(T, H)).astype(np.float32)
    S = rng.normal(size=(H, D, D)).astype(np.float32)
    return q, k, v, g, beta, S


@pytest.mark.parametrize("T,floor_block", [
    (5, None), (37, None), (64, (0, 64)), (100, (0, 64)), (150, (64, 128)),
    (256, (0, 256))])
def test_the_chunked_form_is_the_recurrence(T, floor_block):
    """Prompts that are and are not whole blocks of 64; where a block has
    g at its lower bound throughout, ``exp(-G)`` would be e^320: the
    chunked form stays finite and equal."""
    args = _inputs(T, seed=T, floor_block=floor_block)
    want_o, want_S = _token_scan(*args)
    o, S = jax.jit(kda.chunked)(*args)
    assert np.isfinite(np.asarray(o)).all()
    assert np.abs(np.asarray(o) - want_o).max() < TOL
    assert np.abs(np.asarray(S) - want_S).max() < TOL


def test_the_one_step_form_continues_a_chunked_prefix():
    q, k, v, g, beta, S0 = _inputs(90, seed=3, floor_block=(10, 80))
    want_o, want_S = _token_scan(q, k, v, g, beta, S0)
    _, S = kda.chunked(q[:70], k[:70], v[:70], g[:70], beta[:70], S0)
    for t in range(70, 90):
        o, S = kda.one_step(q[t], k[t], v[t], g[t], beta[t], S)
        assert np.abs(np.asarray(o) - want_o[t]).max() < TOL
    assert np.abs(np.asarray(S) - want_S).max() < TOL
    # g = 0, beta = 0, k = 0 leave a state as it is, to the bit
    zero = jnp.zeros((H, D))
    _, same = kda.one_step(zero, zero, zero, zero, jnp.zeros((H,)), S)
    assert np.array_equal(np.asarray(same), np.asarray(S))


CFG = Ling3Config.tiny()
SIZES = CFG.kda


@pytest.fixture(scope="module")
def layer():
    """A KDA layer's weights and 45 normed rows."""
    p = init_ling3(jax.random.PRNGKey(3), CFG)["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(4), (45, CFG.hidden_size))
    return p, h


def _padded(h, n):
    width = 1 << max(n - 1, 7).bit_length()
    return jnp.zeros((width, h.shape[1]), h.dtype).at[:n].set(h[:n])


def _view(buffers, slots, fresh=None):
    return StateView(StateLayout(1, 4, SIZES.state_parts()), buffers, slots,
                     fresh)


@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_chunks_carried_through_a_state_view_are_one_pass(layer, chunk):
    """41 rows in one program against chunks of 8, 16 and 32 that carry S
    and the conv window in the lane's slot (the last chunk padded), then
    four decode steps of two lanes against the rows of the one pass."""
    p, h = layer
    layout = StateLayout(1, 4, SIZES.state_parts())
    one = _view(layout.zeros(), jnp.int32(1), True)
    want = kda.rows(_padded(h, 45), p, SIZES, one, 0, 45)[:45]
    buffers, got = layout.zeros(), []
    for at in range(0, 41, chunk):
        n = min(chunk, 41 - at)
        view = _view(buffers, jnp.int32(2), at == 0)
        got.append(kda.rows(_padded(h[at:], n), p, SIZES, view, 0, n)[:n])
        buffers = view.buffers
    assert np.abs(np.asarray(jnp.concatenate(got) - want[:41])).max() < TOL
    for t in range(41, 45):
        view = _view(buffers, jnp.asarray([-1, 2], jnp.int32))
        y = kda.step(jnp.stack([h[0], h[t]]), p, SIZES, view, 0)
        assert np.abs(np.asarray(y[1] - want[t])).max() < TOL
        for name in buffers:  # slots no lane owns: written back as read
            assert np.array_equal(np.asarray(view.buffers[name][:, 0]),
                                  np.asarray(buffers[name][:, 0]))
        buffers = view.buffers
    for name, buf in one.buffers.items():
        assert np.abs(np.asarray(buffers[name][0, 2], np.float32)
                      - np.asarray(buf[0, 1], np.float32)).max() < TOL, name


def test_the_seeded_gate_neither_forgets_at_once_nor_never(layer):
    """What `init_ling3` draws the gate for: exp(g) a row spreads, and so
    does beta (the controls of the cell's parity would see nothing of a
    gate stuck at 0 or 1)."""
    p, h = layer
    _, f, b, _ = kda._inputs(h, p, SIZES)
    g, beta = kda._gate(f, b, p, SIZES)
    decay = np.exp(np.asarray(g))
    assert (np.asarray(g) > SIZES.lower_bound).all() and (decay < 1).all()
    assert 0.1 < np.quantile(decay, 0.1) and np.quantile(decay, 0.9) < 0.999
    assert np.quantile(beta, 0.1) < 0.35 and np.quantile(beta, 0.9) > 0.65


# --------------------------------------------------------- the group limit


def _by_hand(scores, bias, k, n_group, topk_group):
    """DeepSeek-V3's `noaux_tc` choice for one row, in plain Python."""
    biased = scores + bias
    size = len(scores) // n_group
    groups = sorted(range(n_group), key=lambda j: -sum(
        sorted(biased[j * size:(j + 1) * size])[-2:]))[:topk_group]
    allowed = [e for j in groups for e in range(j * size, (j + 1) * size)]
    return sorted(sorted(allowed, key=lambda e: -biased[e])[:k])


def test_route_with_a_group_limit_is_the_selection_by_hand():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(40, 12)).astype(np.float32)
    router = rng.normal(size=(12, 32)).astype(np.float32)
    bias = (0.3 * rng.normal(size=(32,))).astype(np.float32)
    weights, experts, counts, probs = moe.route(
        x, router, 4, True, score="sigmoid", select_bias=bias, scale=2.5,
        n_group=8, topk_group=3)
    probs = np.asarray(probs)
    limited = 0
    for row in range(40):
        want = _by_hand(probs[row], bias, 4, 8, 3)
        assert sorted(np.asarray(experts[row]).tolist()) == want
        limited += want != _by_hand(probs[row], bias, 4, 1, 1)
        w = probs[row][np.asarray(experts[row])]
        np.testing.assert_allclose(weights[row], 2.5 * w / w.sum(), rtol=1e-6)
    assert limited > 10  # the limit changed the choice of many rows
    assert int(counts.sum()) == 40 * 4


def _route_at_the_parent(x, router, k, norm_topk, score, select_bias, scale):
    """`moe.route`'s selection as it was before it had a group limit."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1) if score == "softmax" \
        else jax.nn.sigmoid(logits)
    if select_bias is None:
        weights, experts = jax.lax.top_k(probs, k)
    else:
        _, experts = jax.lax.top_k(probs + select_bias, k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * scale if scale != 1.0 else weights, experts


@pytest.mark.parametrize("score,biased,norm_topk,scale", [
    ("softmax", False, False, 1.0),  # OLMoE
    ("softmax", False, True, 1.0),  # granite
    ("sigmoid", True, True, 2.5),  # glm-5, xing4, mimo
    ("sigmoid", True, False, 1.0)])
def test_one_group_is_todays_route_to_the_bit(score, biased, norm_topk, scale):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(33, 12)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(12, 16)), jnp.float32)
    bias = jnp.asarray(0.1 * rng.normal(size=(16,)), jnp.float32) \
        if biased else None
    want_w, want_e = _route_at_the_parent(x, router, 3, norm_topk, score,
                                          bias, scale)
    for groups in ({}, {"n_group": 1, "topk_group": 1}):
        w, e, _, _ = moe.route(x, router, 3, norm_topk, score=score,
                               select_bias=bias, scale=scale, **groups)
        assert np.array_equal(np.asarray(e), np.asarray(want_e))
        assert np.array_equal(np.asarray(w), np.asarray(want_w))
