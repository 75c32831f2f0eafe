"""The paged layer scan carries the KV pools and updates them in place.

``lm._paged_scan`` keeps the whole (L, N, bs, Hkv*hd) pools in the scan's
carry and each layer writes its K/V at ``[layer, blk, off]``. Taken as scan
inputs and stacked back as outputs instead, the pools are sliced per layer
and written into a fresh pool-sized output that donation cannot alias:
every step program then copies the pools. These tests pin both halves:

1. the compiled step program holds no pool-sized copy, broadcast or
   dynamic-update-slice and its temporaries stay well under the pools;
2. the shared scatter writes every layer at its own index, with the K/V the
   non-paged decode path computes for that layer.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm

KERNELS = ("paged_decode_attention", "paged_chunk_attention")


def _instructions(hlo: str):
    """{name: line} of every instruction in an HLO module's text."""
    out = {}
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if m:
            out[m.group(1)] = line
    return out


def _pool_sized_ops(hlo: str, shape):
    """(names of the copy / broadcast / dynamic-update-slice instructions
    that produce a whole pool of ``shape``, number of pool-sized copies the
    Pallas interpret mode makes for its emulated kernels).

    Interpret mode runs a kernel as an XLA while loop that carries every
    operand and writes each block back; XLA copies a pool the loop takes
    if the pool is still live after it. Those copies, and the block
    write-backs inside the loop (named after the kernel), are the
    emulator's, not the step program's: a compiled kernel reads the pool
    in place."""
    insts = _instructions(hlo)
    dims = ",".join(map(str, shape))
    op = re.compile(r"= \w+\[" + dims + r"\]\S* "
                    r"(copy|broadcast|dynamic-update-slice)\(")

    def in_kernel(line):
        return any(f"/{k}/" in line for k in KERNELS)

    def users(name):
        return [n for n, line in insts.items()
                if re.search(rf"%{re.escape(name)}[,)]", line)]

    def only_into(name, test):
        found = users(name)
        return bool(found) and all(test(u) for u in found)

    def kernel_loop(name):
        return " while(" in insts[name] and in_kernel(insts[name])

    def feeds_kernel_loop(name):
        return only_into(name, lambda u: " tuple(" in insts[u]
                         and only_into(u, kernel_loop))

    found, emulator = [], 0
    for name, line in insts.items():
        m = op.search(line)
        if not m or in_kernel(line):
            continue
        if m.group(1) == "copy" and feeds_kernel_loop(name):
            emulator += 1
        else:
            found.append(name)
    return found, emulator


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("phase", ["decode", "prefill"])
def test_step_program_holds_no_copy_of_the_pools(phase, backend):
    """paper-1.5b at reduced widths and 4 layers, 64 blocks of 64, jitted
    as the engine jits it (pools donated)."""
    cfg = dataclasses.replace(get_config("paper-1.5b").reduced(num_layers=4),
                              attn_backend=backend)
    b, width, chunk = (8, 8, 1) if phase == "decode" else (4, 4, 16)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0), cfg))
    pools = jax.eval_shape(lambda: lm.init_paged_cache(cfg, 64, 64))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    if phase == "decode":
        fn = jax.jit(lambda p, pl, bt, sl, t: lm.paged_decode_step(
            p, pl, bt, sl, t, cfg), donate_argnums=(1,))
        args = (i32(b, width), i32(b), i32(b, 1))
    else:
        fn = jax.jit(lambda p, pl, bt, t, nn, st: lm.paged_prefill(
            p, pl, bt, t, nn, cfg, start_lens=st, last_only=True),
            donate_argnums=(1,))
        args = (i32(b, width), i32(b, chunk), i32(b), i32(b))
    compiled = fn.lower(params, pools, *args).compile()
    pool = pools["kpool"]
    one = pool.size * pool.dtype.itemsize
    found, emulator = _pool_sized_ops(compiled.as_text(), pool.shape)
    assert found == [], f"pool-sized ops in the step program: {found}"
    # the interpret emulator copies each of the two pools at most once
    # (the copy is reused across layers); the ref path has no emulator
    assert emulator <= (2 if backend == "interpret" else 0)
    temp = compiled.memory_analysis().temp_size_in_bytes - emulator * one
    assert temp < (2 * one) / 2, (
        f"temporaries {temp} B against pools of {2 * one} B")


def _tiny_cfg(backend):
    return dataclasses.replace(
        get_config("paper-1.5b").reduced(num_layers=3), attn_backend=backend)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_decode_writes_each_layer_at_its_own_index(backend):
    """From zeroed pools, decode steps write layer ``l``'s K/V of token
    ``t`` at exactly ``[l, table[t // bs], t % bs]`` and nowhere else, and
    the written K/V are the ones the non-paged ``init_cache`` +
    ``decode_step`` path computes for that layer on the same tokens."""
    cfg = _tiny_cfg(backend)
    params = lm.init(jax.random.PRNGKey(0), cfg)
    b, steps, bs = 2, 3, 2
    tables = np.asarray([[3, 1], [2, 4]], np.int32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(1, 250,
                                                          (b, steps)))
    pools = lm.init_paged_cache(cfg, num_blocks=5, block_size=bs)
    cache = lm.init_cache(cfg, b, steps)
    for t in range(steps):
        tok = tokens[:, t:t + 1]
        _, cache = lm.decode_step(params, cache, tok, cfg)
        _, pools = lm.paged_decode_step(params, pools, jnp.asarray(tables),
                                        jnp.full((b,), t, jnp.int32), tok,
                                        cfg)
    for name, dense in (("kpool", cache["k"]), ("vpool", cache["v"])):
        want = np.zeros(pools[name].shape, np.float32)
        for row in range(b):
            for t in range(steps):
                want[:, tables[row, t // bs], t % bs] = \
                    np.asarray(dense[:, row, t]).reshape(cfg.num_layers, -1)
        got = np.asarray(pools[name])
        # exactly the written rows are non-zero; every layer its own
        np.testing.assert_array_equal(got != 0, want != 0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
