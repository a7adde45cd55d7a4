"""Arm decision rules on crafted distributions (paper Table 1 semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.arms import (ADAEDL_DEFAULTS, arm_by_name, default_pool,
                             multi_threshold_pool, signal_vector,
                             signals_from_probs, update_adaedl_lambda)


def _sig(probs, prev_ent=0.0, lam=0.4, pos=1):
    p = jnp.asarray(probs)[None]          # batch of 1
    return signals_from_probs(p, jnp.asarray([prev_ent]), lam, pos)


def _stop(arm_name, sig, threshold=None):
    return bool(np.asarray(arm_by_name(arm_name, threshold).fn(sig))[0])


def test_max_confidence_stops_on_low_top1():
    assert _stop("max_confidence", _sig([0.5, 0.3, 0.2]))       # top1 .5 < .8
    assert not _stop("max_confidence", _sig([0.9, 0.05, 0.05]))


def test_svip_stops_on_high_entropy():
    flat = [1 / 8] * 8                     # H = ln 8 ~ 2.08, sqrt ~ 1.44 > .6
    assert _stop("svip", _sig(flat))
    peaked = [0.99] + [0.01 / 7] * 7
    assert not _stop("svip", _sig(peaked))


def test_logit_margin():
    assert _stop("logit_margin", _sig([0.45, 0.40, 0.15]))      # margin .05
    assert not _stop("logit_margin", _sig([0.8, 0.1, 0.1]))


def test_svip_difference_detects_spike():
    flat = [1 / 8] * 8
    s = _sig(flat, prev_ent=0.1)
    assert _stop("svip_difference", s)                           # 1.44-.1 > .2
    s2 = _sig(flat, prev_ent=1.40)
    assert not _stop("svip_difference", s2)


def test_adaedl_lambda_controls_stopping():
    flat = [1 / 8] * 8
    # 1 - sqrt(H) ~ 1-1.44 < 0: stops for lam=0.4, not for lam=-1 equivalent
    assert _stop("adaedl", _sig(flat, lam=0.4))
    peaked = [0.999] + [0.001 / 7] * 7
    assert not _stop("adaedl", _sig(peaked, lam=0.4))


def test_adaedl_update_direction():
    lam, ema = update_adaedl_lambda(0.4, 0.8, n_acc=0, n_drafted=8)
    assert lam > 0.4            # low accept rate -> raise threshold (stop earlier)
    lam2, _ = update_adaedl_lambda(0.4, 0.8, n_acc=8, n_drafted=8)
    assert lam2 < 0.4           # perfect acceptance -> relax


def test_default_pool_is_paper_table1():
    pool = default_pool()
    names = [a.name for a in pool]
    assert names == ["max_confidence", "svip", "adaedl", "svip_difference",
                     "logit_margin"]
    th = {a.name: a.threshold for a in pool}
    assert th["max_confidence"] == 0.8 and th["svip"] == 0.6
    assert th["svip_difference"] == 0.2 and th["logit_margin"] == 0.2


def test_multi_threshold_pool_bigger():
    assert len(multi_threshold_pool()) == 13


def test_arm_identity_cached_for_jit():
    assert default_pool()[0].fn is default_pool()[0].fn
    assert arm_by_name("svip") is arm_by_name("svip")


def test_signal_vector_shape():
    sig = _sig([0.5, 0.3, 0.2])
    v = signal_vector(sig)
    assert v.shape == (1, 6)
    assert np.isfinite(np.asarray(v)).all()


def _softmax_rows(shape, seed=0):
    logits = np.random.default_rng(seed).normal(0, 3, shape)
    return np.asarray(jax.nn.softmax(jnp.asarray(logits, jnp.float32), -1))


def _row(v, at, vals):
    p = np.zeros((1, v), np.float32)
    p[0, at] = vals
    return p


@pytest.mark.parametrize("probs", [
    pytest.param(lambda: _softmax_rows((4, 151936)), id="softmax-B-V151936"),
    pytest.param(lambda: _softmax_rows((2, 3, 151936), 1),
                 id="softmax-B-S-V151936"),
    pytest.param(lambda: _softmax_rows((5, 257), 2), id="softmax-B-V257"),
    pytest.param(lambda: _softmax_rows((2, 4, 257), 3), id="softmax-B-S-V257"),
    pytest.param(lambda: _row(257, [3, 200, 7], [0.4, 0.4, 0.2]),
                 id="two-tied-maxima"),
    pytest.param(lambda: _row(257, [5], [1.0]), id="one-hot"),
    pytest.param(lambda: _row(257, [0, 100, 256], [0.2, 0.3, 0.5]),
                 id="max-at-last-index"),
])
def test_top1_top2_match_lax_top_k_bitwise(probs):
    p = jnp.asarray(probs())
    lead = p.shape[:-1]
    sig = jax.jit(lambda q: signals_from_probs(
        q, jnp.zeros(lead), 0.4, 1))(p)
    want = np.asarray(jax.lax.top_k(p, 2)[0])
    for k, name in enumerate(("top1", "top2")):
        got = np.asarray(sig[name])
        assert got.shape == lead
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want[..., k].view(np.uint32))
