"""The Mamba-2 mixer (models/ssm.py) at a tiny size on the CPU, float32:
the chunked scan, the one-token recurrence and the plain reference
(chipbench/reference/falcon_h1.py) give the same numbers, for lengths that
are and are not multiples of the chunk; a scan split over two calls through
the cache is one call; positions past a row's last real token leave the
state and the convolution's tail alone."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import families, weights
from chipbench.reference import falcon_h1 as ref
from k8s_device_plugin_tpu.models import ssm
from k8s_device_plugin_tpu.models.transformer import GPTConfig

# Hidden 64, 4 mixer heads of 16, state 16, 2 groups, chunk 8, float32: the
# tiny cell's published keys, and the program's config of them as the
# benchmark's family builds it.
with open(os.path.join(os.path.dirname(__file__), "chipbench", "data", "tiny-falcon-h1.json")) as f:
    MODEL = json.load(f)
FAMILY = families.load("falcon_h1")
CFG = FAMILY.build(MODEL, MODEL["engine"])[0]
MIXER = CFG.mixer
LENGTHS = [1, 3, 8, 13, 16, 21]  # chunk 8: below, at, between and at multiples


def _inputs(batch, t_len, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    x, b, c = f(batch, t_len, 4, 16), f(batch, t_len, 2, 16), f(batch, t_len, 2, 16)
    dt = jax.nn.softplus(f(batch, t_len, 4) - 2.0)
    a_neg = -jnp.asarray(rng.uniform(1.0, 16.0, 4), jnp.float32)
    h0 = 0.1 * f(batch, 4, 16, 16)
    return x, dt, a_neg, b, c, h0


def _stepwise(x, dt, a_neg, b, c, h):
    ys = []
    for t in range(x.shape[1]):
        y, h = ssm.ssd_step(x[:, t], dt[:, t], a_neg, b[:, t], c[:, t], h)
        ys.append(y)
    return jnp.stack(ys, axis=1), h


def _numpy_recurrence(x, dt, a_neg, b, c, h):
    """The equations as loops, head by head: h = exp(dt A) h + dt x (x) B."""
    x, dt, a_neg, b, c, h = (np.asarray(v, np.float64) for v in (x, dt, a_neg, b, c, h))
    batch, t_len, heads, p = x.shape
    rep = heads // b.shape[2]
    y = np.zeros((batch, t_len, heads, p))
    for i in range(batch):
        for head in range(heads):
            state = h[i, head].copy()
            for t in range(t_len):
                state = np.exp(dt[i, t, head] * a_neg[head]) * state + dt[i, t, head] * np.outer(
                    x[i, t, head], b[i, t, head // rep])
                y[i, t, head] = state @ c[i, t, head // rep]
            h[i, head] = state
    return y, h


@pytest.mark.parametrize("t_len", LENGTHS)
def test_chunked_scan_is_the_recurrence(t_len):
    args = _inputs(2, t_len, seed=t_len)
    y_scan, h_scan = jax.jit(ssm.ssd_scan, static_argnames="chunk")(*args, chunk=8)
    y_step, h_step = jax.jit(_stepwise)(*args)
    y_np, h_np = _numpy_recurrence(*args)
    np.testing.assert_allclose(y_scan, y_step, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_scan, h_step, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(y_step, y_np, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h_step, h_np, rtol=2e-4, atol=2e-5)


def test_a_frozen_position_leaves_the_state_alone():
    x, dt, a_neg, b, c, h0 = _inputs(1, 12)
    dt = dt.at[:, 7:].set(0.0)
    _, h_all = ssm.ssd_scan(x, dt, a_neg, b, c, h0, chunk=8)
    _, h_cut = ssm.ssd_scan(x[:, :7], dt[:, :7], a_neg, b[:, :7], c[:, :7], h0, chunk=8)
    np.testing.assert_allclose(h_all, h_cut, rtol=1e-5, atol=1e-6)


_PARAMS_TREE = jax.jit(lambda words: FAMILY.params_tree(MODEL, words))


@functools.lru_cache(maxsize=None)
def mixer_params(seed):
    """The reference's leaves of layer 0 and the mixer's flax params of
    the same seed, as the family lays them out."""
    tree = _PARAMS_TREE(weights.seed_words(seed))
    return ref.layer_leaves(MODEL, seed, 0), tree["layer_0"]["mixer"]


def _hidden(batch, t_len, seed):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((batch, t_len, 64)), jnp.float32)


def _positions(batch, lo, hi):
    return jnp.broadcast_to(jnp.arange(lo, hi)[None], (batch, hi - lo))


@pytest.mark.parametrize("t_len", LENGTHS)
def test_module_is_the_reference_mixer(t_len):
    """Whole sequence from a zero state: the module (chunked scan) against
    the reference's token-by-token ``lax.scan``."""
    w, params = mixer_params(seed=5)
    u = _hidden(2, t_len, seed=t_len)
    got = jax.jit(ssm.Mamba2Mixer(CFG).apply)({"params": params}, u, _positions(2, 0, t_len))
    w32 = {name: leaf.astype(jnp.float32) for name, leaf in w.items()}
    for row in range(2):
        want = ref.mixer(MODEL, w32, u[row] / MODEL["ssm_in_multiplier"], None)
        np.testing.assert_allclose(got[row], want, rtol=2e-3, atol=2e-4)


def _decode_mixer():
    return ssm.Mamba2Mixer(CFG, decode=True)


def _zero_cache(batch):
    return {"slot_ssm": jnp.zeros((batch, 4, 16, 16), jnp.float32), "slot_conv": jnp.zeros((batch, 3, 128), jnp.float32)}


@jax.jit
def _apply(params, cache, u, positions, last):
    return _decode_mixer().apply({"params": params, "cache": cache}, u, positions, last, mutable=["cache"])


def _call(params, cache, u, lo, last=None):
    out, mut = _apply(params, cache, u, _positions(u.shape[0], lo, lo + u.shape[1]),
                      None if last is None else jnp.asarray(last, jnp.int32))
    return out, mut["cache"]


@pytest.mark.parametrize("split", [1, 2, 8, 11, 16, 19])
def test_a_scan_split_over_two_calls_is_one_call(split):
    """20 tokens in one call, against ``split`` and the rest through the
    cache (a split of 1 or 19 leaves one call to the one-token path)."""
    _, params = mixer_params(seed=6)
    u = _hidden(2, 20, seed=1)
    whole, cache_whole = _call(params, _zero_cache(2), u, 0)
    first, cache = _call(params, _zero_cache(2), u[:, :split], 0)
    second, cache = _call(params, cache, u[:, split:], split)
    np.testing.assert_allclose(jnp.concatenate([first, second], axis=1), whole, rtol=2e-3, atol=2e-4)
    for leaf in ("slot_ssm", "slot_conv"):
        np.testing.assert_allclose(cache[leaf], cache_whole[leaf], rtol=2e-3, atol=2e-5)


@pytest.mark.parametrize("plen", list(range(9, 17)) + [2, 17, 23])
def test_padding_past_the_last_position_never_reaches_the_state(plen):
    """A prompt padded to 24 and run as chunks of 8 with ``last_positions``
    leaves the state and the tail that the unpadded prompt leaves, and the
    same outputs at the real positions: lengths through one bucket, one
    whose last chunk holds fewer real inputs than the tail is long (17: the
    tail reaches into the previous chunk), one of fewer than a tail."""
    _, params = mixer_params(seed=7)
    u = _hidden(1, 24, seed=plen)
    want, cache_want = _call(params, _zero_cache(1), u[:, :plen], 0)
    cache, outs = _zero_cache(1), []
    for lo in range(0, 24, 8):
        out, cache = _call(params, cache, u[:, lo : lo + 8], lo, last=[plen - 1])
        outs.append(out)
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1)[:, :plen], want, rtol=2e-3, atol=2e-4)
    for leaf in ("slot_ssm", "slot_conv"):
        np.testing.assert_allclose(cache[leaf], cache_want[leaf], rtol=2e-3, atol=2e-5)
    # Without the mask the pad tokens do reach the state: the trap is real.
    cache = _zero_cache(1)
    for lo in range(0, 24, 8):
        _, cache = _call(params, cache, u[:, lo : lo + 8], lo)
    assert float(jnp.abs(cache["slot_ssm"] - cache_want["slot_ssm"]).max()) > 1e-3


def test_decode_steps_continue_a_prefill():
    _, params = mixer_params(seed=8)
    u = _hidden(2, 14, seed=3)
    whole, cache_whole = _call(params, _zero_cache(2), u, 0)
    _, cache = _call(params, _zero_cache(2), u[:, :9], 0)
    for t in range(9, 14):
        out, cache = _call(params, cache, u[:, t : t + 1], t)
        np.testing.assert_allclose(out[:, 0], whole[:, t], rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(cache["slot_ssm"], cache_whole["slot_ssm"], rtol=2e-3, atol=2e-5)


def test_config_checks():
    with pytest.raises(ValueError, match="d_ssm"):
        ssm.MambaConfig(d_ssm=60, n_heads=4, head_dim=16)
    with pytest.raises(ValueError, match="n_groups"):
        ssm.MambaConfig(d_ssm=48, n_heads=3, head_dim=16, n_groups=2)
    assert MIXER.conv_dim == 128 and MIXER.in_features == 64 + 128 + 4
    assert GPTConfig.tiny().head_dim == 16 and CFG.head_dim == 32
