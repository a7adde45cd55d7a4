"""Speculative decoding engine: exactness, rollback paths, accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ar_greedy_decode
from repro.core import (ModelBundle, SpecEngine, StaticGamma, make_controller)
from repro.models import ModelConfig, RGLRUConfig, SSMConfig
from repro.models import transformer as T

PROMPT = [1, 5, 9, 13]


@pytest.mark.parametrize("ckind", ["static", "fixed_svip", "fixed_max_confidence",
                                   "fixed_adaedl", "tapout_seq_ucb1",
                                   "tapout_seq_ts", "tapout_token_ucb1",
                                   "tapout_token_ts", "tapout_seq_ucb_tuned"])
def test_greedy_equivalence_all_controllers(ckind, tiny_dense_pair):
    draft, target = tiny_dense_pair
    ref = ar_greedy_decode(target.params, target.cfg, PROMPT, 40)
    ctrl = make_controller(ckind, gamma_max=8, seed=0)
    eng = SpecEngine(draft, target, ctrl, max_len=256)
    r = eng.generate(PROMPT, 40)
    assert r.tokens[:len(ref)] == ref[:len(r.tokens)]
    assert r.new_tokens >= 40
    # accounting invariants
    for s in r.sessions:
        assert 0 <= s.n_accepted <= s.n_drafted <= ctrl.gamma_max
    # every session emits exactly m+1 tokens
    assert r.total_accepted + len(r.sessions) == r.new_tokens


def test_greedy_equivalence_recurrent_family():
    V = 61
    tcfg = ModelConfig(name="t", arch_type="ssm", num_layers=3, d_model=128,
                       num_heads=1, num_kv_heads=1, d_ff=0, vocab_size=V,
                       block_pattern=("mamba2",),
                       ssm=SSMConfig(d_state=16, head_dim=32, chunk_size=8))
    dcfg = ModelConfig(name="d", arch_type="hybrid", num_layers=3, d_model=64,
                       num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=V,
                       block_pattern=("rglru", "rglru", "local"), window=16,
                       rglru=RGLRUConfig(lru_width=64))
    tp = T.init_params(tcfg, jax.random.PRNGKey(0))
    dp = T.init_params(dcfg, jax.random.PRNGKey(1))
    ref = ar_greedy_decode(tp, tcfg, PROMPT, 24)
    eng = SpecEngine(ModelBundle(dp, dcfg), ModelBundle(tp, tcfg),
                     make_controller("tapout_seq_ucb1", gamma_max=6), max_len=128)
    assert not eng.draft_cheap and not eng.target_cheap  # recompute path
    r = eng.generate(PROMPT, 24)
    assert r.tokens[:len(ref)] == ref[:len(r.tokens)]


def test_self_speculation_accepts_everything(tiny_dense_pair):
    _, target = tiny_dense_pair
    eng = SpecEngine(target, target, StaticGamma(gamma=6), max_len=256)
    r = eng.generate(PROMPT, 30)
    assert r.accept_rate == 1.0
    assert r.mean_accepted == 6.0


def test_static_gamma_always_drafts_exactly_gamma(tiny_dense_pair):
    draft, target = tiny_dense_pair
    eng = SpecEngine(draft, target, StaticGamma(gamma=5), max_len=256)
    r = eng.generate(PROMPT, 25)
    assert all(s.n_drafted == 5 for s in r.sessions)


def test_stochastic_output_distribution(tiny_dense_pair):
    """Exact speculative sampling: empirical next-token dist ~= target dist."""
    draft, target = tiny_dense_pair
    cache, spec = T.init_cache(target.cfg, 1, 64, jnp.float32)
    lg, _ = T.step(target.params, target.cfg,
                   jnp.asarray([PROMPT], jnp.int32), cache, spec)
    p_tgt = np.asarray(jax.nn.softmax(lg[0, -1]))
    N = 250
    eng = SpecEngine(draft, target, StaticGamma(gamma=3), max_len=64,
                     temperature=1.0, greedy=False, seed=0)
    counts = np.zeros(target.cfg.vocab_size)
    for _ in range(N):
        r = eng.generate(PROMPT, 1)
        counts[r.tokens[len(PROMPT)]] += 1
    tv = 0.5 * np.abs(counts / N - p_tgt).sum()
    assert tv < 0.22, tv


def test_traces_collected(tiny_dense_pair):
    draft, target = tiny_dense_pair
    eng = SpecEngine(draft, target, StaticGamma(gamma=4), max_len=128)
    eng.collect_traces = True
    r = eng.generate(PROMPT, 12)
    assert len(r.traces) == len(r.sessions)
    tr = r.traces[0]
    assert tr["signals"].shape == (4, 6)
    assert tr["n_drafted"] == 4


def test_modeled_cost_monotone(tiny_dense_pair):
    draft, target = tiny_dense_pair
    eng = SpecEngine(draft, target, StaticGamma(gamma=6), max_len=256)
    r1 = eng.generate(PROMPT, 10)
    r2 = eng.generate(PROMPT, 30)
    assert r2.modeled_cost > r1.modeled_cost > 0


def test_greedy_probs_are_the_exact_argmax_one_hot():
    """At temperature 0 the target/draft distribution is the one-hot of the
    logits' argmax, computed exactly: no tiny-temperature softmax, whose
    scaled logits (~1e5 here) are where a backend can lose the argmax."""
    from repro.core.spec_decode import _probs
    logits = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4099)) * 4.0
    logits = logits.astype(jnp.bfloat16)
    p = np.asarray(_probs(logits, 0.0))
    want = np.eye(4099, dtype=np.float32)[np.asarray(
        logits.astype(jnp.float32)).argmax(-1)]
    np.testing.assert_array_equal(p, want)
    warm = np.asarray(_probs(logits, 1.0))
    np.testing.assert_allclose(warm.sum(-1), 1.0, rtol=1e-5)
