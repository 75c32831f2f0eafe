"""Multi-device integration tests (8 host CPU devices via subprocess, so the
main pytest process keeps its single-device view). The subprocess harness
is CPU-only by construction: it pins JAX_PLATFORMS=cpu and forces the host
platform's device count."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(script: str, timeout=560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"        # CPU-only: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_train_step_executes_on_pod_mesh():
    """Real execution (not just compile) of a sharded train step on a
    (pod=2, data=2, model=2) mesh: FSDP+TP+DP all engaged."""
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, AxisType
from repro.configs import get_config
from repro.config import TrainConfig
from repro.models import lm
from repro.optim import adamw
from repro.distributed.sharding import make_param_specs, named, batch_spec
from repro import training

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
cfg = get_config("paper-0.5b").reduced(d_model=64, d_ff=128, num_layers=2,
                                       num_heads=4, head_dim=16)
key = jax.random.PRNGKey(0)
with jax.set_mesh(mesh):
    params = lm.init(key, cfg)
    pspecs = make_param_specs(jax.eval_shape(lambda: params), cfg, mesh)
    params = jax.device_put(params, named(mesh, pspecs))
    opt = adamw.init(params)
    opt = jax.device_put(opt, named(mesh, adamw.AdamWState(P(), pspecs, pspecs)))
    batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
    bs = {k: jax.device_put(v, named(mesh, batch_spec(v.ndim, mesh, v.shape[0])))
          for k, v in batch.items()}
    step = jax.jit(training.make_train_step(cfg, TrainConfig()))
    p2, o2, m = step(params, opt, bs)
    l0 = float(m["loss"])
    for _ in range(3):
        p2, o2, m = step(p2, o2, bs)
    assert float(m["loss"]) < l0, (l0, float(m["loss"]))
    print("LOSS_OK", l0, float(m["loss"]))
""")
    assert "LOSS_OK" in out


def test_moe_sorted_matches_onehot_on_mesh():
    """Sorted shard_map dispatch == exact one-hot dispatch when capacity is
    generous (no drops)."""
    out = _run("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding, AxisType
from repro.configs import get_config
from repro.models import moe
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
cfg = get_config("mixtral-8x22b").reduced(d_model=32, d_ff=64, num_experts=4,
                                          top_k=2)
cfg = dataclasses.replace(cfg, capacity_factor=8.0)   # no drops
key = jax.random.PRNGKey(0)
p = moe.moe_init(key, cfg.d_model, cfg.d_ff, 4, True, jnp.float32)
x = jax.random.normal(key, (4, 8, cfg.d_model))
with jax.set_mesh(mesh):
    ps = jax.device_put(p, NamedSharding(mesh, P()))
    xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    y_sorted, aux_s = jax.jit(lambda p, x: moe.moe_apply_sorted(
        p, x, cfg, cfg.sparsity, True, mesh, ("data",)))(ps, xs)
y_ref, aux_r = moe.moe_apply_onehot(p, x, cfg, cfg.sparsity, True)
np.testing.assert_allclose(np.asarray(y_sorted), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
assert float(aux_s["moe_drop_frac"]) == 0.0
print("MOE_MATCH", float(jnp.abs(y_sorted - y_ref).max()))
""")
    assert "MOE_MATCH" in out


def test_compressed_psum_across_pods():
    """int8 error-feedback psum over the pod axis: per-step quantization
    error is bounded, and accumulated error feedback keeps the long-run
    average unbiased."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding, AxisType
from repro.optim.compress import compressed_psum, init_error_state
mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
g = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 8))}
e = init_error_state(g)
with jax.set_mesh(mesh):
    red, err = jax.jit(lambda g, e: compressed_psum(
        g, e, mesh, axis="pod", method="int8"))(g, e)
# replicated input over pods -> mean == input, up to int8 quantization
scale = float(jnp.abs(g["w"]).max()) / 127.0
assert float(jnp.abs(red["w"] - g["w"]).max()) <= scale * 0.51 + 1e-6
np.testing.assert_allclose(np.asarray(red["w"] + err["w"]),
                           np.asarray(g["w"]), rtol=1e-5, atol=1e-6)
print("PSUM_OK")
""")
    assert "PSUM_OK" in out


@pytest.mark.parametrize("arch", ["olmo-1b", "mixtral-8x22b", "rwkv6-7b"])
def test_mini_dryrun_cell(arch):
    """The dry-run machinery end-to-end on a small mesh: lower + compile +
    analyses succeed for train and decode kinds."""
    out = _run(f"""
import dataclasses, jax, jax.numpy as jnp
from jax.sharding import AxisType
# reuse the dryrun internals against a small mesh via monkeypatch
import repro.launch.dryrun as dr
import repro.launch.mesh as mesh_mod
mesh_mod.make_production_mesh = lambda multi_pod=False: jax.make_mesh(
    (2, 2, 2) if multi_pod else (2, 4),
    ("pod", "data", "model") if multi_pod else ("data", "model"),
    axis_types=(AxisType.Auto,) * (3 if multi_pod else 2))
dr.make_production_mesh = mesh_mod.make_production_mesh
import repro.configs as C
_orig = C.get_config
import repro.launch.dryrun as d2
get_reduced = lambda name: _orig(name).reduced(num_layers=2)
d2.get_config = get_reduced
import repro.config as rc
small = dataclasses.replace(rc.shape_by_name("train_4k"), seq_len=64,
                            global_batch=8)
rc_shapes = {{s.name: s for s in rc.LM_SHAPES}}
d2.shape_by_name = lambda n: dataclasses.replace(
    rc_shapes[n], seq_len=64, global_batch=8)
rec = d2.run_cell("{arch}", "train_4k", multi_pod=False)
assert rec["dot_flops_per_device"] > 0
rec2 = d2.run_cell("{arch}", "decode_32k", multi_pod=True)
print("MINI_DRYRUN_OK", rec["dot_flops_per_device"],
      rec2["collective_bytes_per_device"]["total"])
""")
    assert "MINI_DRYRUN_OK" in out


def test_param_spec_rules():
    """Rule-engine regression: EP lands on the expert dim (-3) of
    layer-stacked weights, never the layer dim; FSDP composes."""
    import jax
    from jax.sharding import AbstractMesh, AxisType
    from repro.configs import get_config
    from repro.distributed.sharding import param_spec

    mesh = AbstractMesh((2, 4), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("llama4-scout-17b-a16e")
    # llama4: 16 experts, EP divisible by model axis in production; with a
    # 4-way model axis here 16 % 4 == 0 still -> EP
    sp = param_spec("blocks/moe/experts/wu", (48, 16, 5120, 8192), cfg, mesh)
    assert sp[1] == "model" and sp[0] is None, sp     # expert dim, not layer
    sp = param_spec("blocks/moe/experts/wd", (48, 16, 8192, 5120), cfg, mesh)
    assert sp[1] == "model" and sp[0] is None, sp
    # mixtral: 8 experts % 16 != 0 at production tp; per-expert TP instead
    cfg2 = get_config("mixtral-8x22b")
    mesh16 = AbstractMesh((1, 8), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    sp = param_spec("blocks/moe/experts/wu", (56, 8, 6144, 16384), cfg2,
                    mesh16)
    assert sp[1] == "model" or sp[-1] == "model"
    # attention heads divisible -> column TP on flattened heads
    cfg3 = get_config("deepseek-67b")
    sp = param_spec("blocks/attn/wq", (95, 8192, 8192), cfg3, mesh)
    assert sp[-1] == "model"
    # norms replicated TP-wise, FSDP may take a dim
    sp = param_spec("blocks/ln1/scale", (95, 8192), cfg3, mesh)
    assert "model" not in tuple(sp)


def test_flash_decode_attention_sharded():
    """Explicit seq-sharded flash-decode attention == single-device SDPA."""
    out = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding, AxisType
from repro.distributed.collectives import flash_decode_attention
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,)*2)
key = jax.random.PRNGKey(0)
B, S, H, hd = 2, 64, 4, 16
q = jax.random.normal(key, (B, 1, H, hd))
k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd))
v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd))
length = jnp.int32(40)
with jax.set_mesh(mesh):
    ks = jax.device_put(k, NamedSharding(mesh, P(None, "model", None, None)))
    vs = jax.device_put(v, NamedSharding(mesh, P(None, "model", None, None)))
    out_sh = jax.jit(lambda q, k, v, l: flash_decode_attention(
        q, k, v, l, mesh))(q, ks, vs, length)
# reference: masked SDPA over the valid prefix
scale = 1.0 / hd ** 0.5
logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
logits = jnp.where((jnp.arange(S) < length)[None, None, None], logits, -1e30)
p = jax.nn.softmax(logits, -1)
ref = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
np.testing.assert_allclose(np.asarray(out_sh), np.asarray(ref),
                           rtol=2e-3, atol=2e-3)
print("FLASH_DECODE_OK")
""")
    assert "FLASH_DECODE_OK" in out
