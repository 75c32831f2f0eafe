"""Fig. 6 + Fig. 7 reproduction: where sparsity lives.

Fig. 6: per-layer nnz mean/max from a trained sparse model + each layer's
modeled speed-up contribution (dead-tile fraction -> skipped MXU work, the
paper's 'relative speedup' axis; the paper reports Pearson < -0.996 between
layer nnz and speedup — we compute the same correlation on the model).

Fig. 7: average nnz by sequence position (the paper finds early positions
excite far more neurons) and highest/lowest-activity tokens.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BATCH, SEQ, emit, tiny_cfg, train_tiny
from repro.core import twell
from repro.data.pipeline import SyntheticLM
from repro.models import lm

OUT = os.path.join(os.path.dirname(__file__), "..", "results",
                   "bench_fig6_fig7.json")


def run(steps=250):
    cfg = tiny_cfg(l1=3.0, layers=4)
    r = train_tiny(cfg, steps=steps)
    params = r["params"]

    # --- Fig. 6: per-layer stats ------------------------------------------
    data = SyntheticLM(cfg.vocab_size, BATCH, SEQ, seed=42)
    batch = {k: jnp.asarray(v) for k, v in next(data).items()}
    _, aux = jax.jit(lambda p, b: lm.forward(p, b, cfg))(params, batch)
    nnz_mean = np.asarray(aux["nnz_mean"])                  # (L,)
    nnz_max = np.asarray(aux["nnz_max"])
    # modeled per-layer speedup: dense-equivalent active fraction
    speedup = 1.0 / np.maximum(nnz_mean / cfg.d_ff, 1e-3)
    corr = float(np.corrcoef(nnz_mean, 1.0 / speedup)[0, 1])
    for i, (m, mx, s) in enumerate(zip(nnz_mean, nnz_max, speedup)):
        emit(f"fig6_layer{i}", 0.0,
             f"nnz_mean={m:.1f};nnz_max={mx};modeled_speedup={s:.2f}")
    emit("fig6_pearson_nnz_vs_invspeedup", 0.0, f"corr={corr:.4f}")

    # --- Fig. 7: nnz by position / by token --------------------------------
    # collect the first layer's hidden activations explicitly
    from repro.core import sparse_ffn
    from repro.models.layers import norm_apply
    blocks = params["blocks"]
    p0 = jax.tree.map(lambda a: a[0], blocks)
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    h_in = norm_apply(cfg.norm, p0["ln2"], x)
    _, aux0 = sparse_ffn.apply(p0["ffn"], h_in, cfg.sparsity, cfg.gated)
    act = jax.nn.relu(h_in.reshape(-1, cfg.d_model) @ p0["ffn"]["wg"])
    nnz_tok = np.asarray((act > 0).sum(-1)).reshape(BATCH, SEQ)
    by_pos = nnz_tok.mean(axis=0)
    emit("fig7_position_curve", 0.0,
         f"pos0={by_pos[0]:.1f};pos_mid={by_pos[SEQ//2]:.1f};"
         f"pos_last={by_pos[-1]:.1f};"
         f"early_over_late={by_pos[:4].mean()/max(by_pos[-4:].mean(),1e-9):.2f}")
    toks = np.asarray(batch["tokens"]).reshape(-1)
    flat = nnz_tok.reshape(-1)
    per_tok = {}
    for t, n in zip(toks, flat):
        per_tok.setdefault(int(t), []).append(float(n))
    avg = {t: float(np.mean(v)) for t, v in per_tok.items() if len(v) >= 2}
    srt = sorted(avg.items(), key=lambda kv: kv[1])
    emit("fig7_token_extremes", 0.0,
         f"lowest={srt[:3]};highest={srt[-3:]}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump({"fig6": {"nnz_mean": nnz_mean.tolist(),
                            "nnz_max": nnz_max.tolist(),
                            "modeled_speedup": speedup.tolist(),
                            "pearson": corr},
                   "fig7": {"by_pos": by_pos.tolist(),
                            "token_lowest": srt[:6],
                            "token_highest": srt[-6:]}}, f, indent=1)
    return corr


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
