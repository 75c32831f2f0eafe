"""The plain reference computes the program's model: at a small size on
the CPU it matches ``repro.models.lm.forward`` over the same weights (made by
the benchmark from the seed), for both configurations' block kinds."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import measure, model

SMALL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 512, "vocab_size": 256, "gated": True,
         "activation": "relu", "norm": "rmsnorm", "rope_theta": 10000.0,
         "tied_embeddings": True, "dtype": "float32", "twell_tile": 256,
         "twell_c": 8}
WEIGHTS = {"init_std": 0.1, "live_gate_share": 0.0625}


@pytest.mark.parametrize("preset,norm,gated,kv", [
    ("paper-1.5b", "rmsnorm", True, 4),
    ("olmo-1b", "nonparametric_ln", True, 4),
    ("olmo-1b", "nonparametric_ln", False, 2),
])
@pytest.mark.parametrize("impl", ["dense", "gather"])
def test_reference_matches_the_program(preset, norm, gated, kv, impl):
    from repro.configs import get_config
    from repro.models import lm
    m = dict(SMALL, norm=norm, gated=gated, num_kv_heads=kv)
    cfg = measure.model_config(get_config(preset), m)
    cfg = dataclasses.replace(cfg, remat="none", sparsity=dataclasses.replace(
        cfg.sparsity, ffn_impl=impl))
    params, live = model.make_params(m, WEIGHTS, 2 ** 31 + 7)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg)))
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 48),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = lm.forward(params, {"tokens": toks[None]}, cfg)
    ref = model.forward(m, params, toks)
    np.testing.assert_allclose(np.asarray(prog[0]), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_weights_are_a_function_of_the_seed():
    a, live_a = model.make_params(SMALL, WEIGHTS, 123)
    b, live_b = model.make_params(SMALL, WEIGHTS, 123)
    c, _ = model.make_params(SMALL, WEIGHTS, 124)
    same = jax.tree.map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool(jnp.all(a["embed"] == c["embed"]))
    assert int(live_a.sum()) == 2 * round(512 / 16)
    wg = np.asarray(a["blocks"]["ffn"]["wg"])
    assert np.all(wg[:, :, ~np.asarray(live_a)[0]][0] == 0)


def test_overflow_bound():
    live = np.zeros((2, 512), bool)
    live[0, :33] = True
    assert model.overflow_bound(live, 256, 32) == (33, 32)
    assert model.overflow_bound(live[:, ::2], 128, 32)[0] == 17


def test_gaps_score_served_tokens():
    m = SMALL
    params, _ = model.make_params(m, WEIGHTS, 9)
    ref = model.Reference(m, pad_to=64, rows=8)
    prompt = list(range(10, 30))
    greedy = []
    toks = list(prompt)
    for _ in range(4):
        lg = np.asarray(model.forward(m, params, jnp.asarray(toks)))
        greedy.append(int(lg[-1].argmax()))
        toks.append(greedy[-1])
    full = np.asarray(model.forward(m, params, jnp.asarray(toks)))
    rows = ref.logits(params, prompt, greedy)
    np.testing.assert_allclose(np.asarray(rows)[:4], full[19:23],
                               atol=1e-5, rtol=1e-5)
    pad = np.zeros(8, np.int32)
    pad[:4] = greedy
    s = {k: np.asarray(v) for k, v in model.score(rows, rows, pad, 4).items()}
    assert np.all(np.abs(s["gap"]) < 1e-5) and not s["rel_err"].any()
    # a token other than the best at the first served position
    pad[0] = (greedy[0] + 1) % 256
    assert np.asarray(model.score(rows, rows, pad, 4)["gap"])[0] > 0
    # the control's logits lie measurably off the reference's
    ctrl = model.score(rows, ref.control_logits(params, prompt, greedy),
                       pad, 4)
    assert 1e-3 < float(np.asarray(ctrl["rel_err"])[:4].max()) < 0.5
