"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode (how every other kernel test runs) cannot see what the
TPU's compiler refuses: slices not aligned to the (8, 128) tiling, rank-1
dots, scalar tables that overflow SMEM.  These tests compile each kernel
of the main path, at real widths, for a described `v5e:2x2` topology —
no chip needed — and check that the HLO holds the Mosaic kernel
(`tpu_custom_call`).  Nothing runs, so nothing here speaks to results or
times.

Only one process at a time may load the TPU compiler's library, so the
topology is described inside a module fixture (never at import time), and
these tests stay in this one file.
"""
import os
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import gossip, local
from repro.kernels.gossip_gather import gossip_gather_pallas
from repro.kernels.gossip_scatter import gossip_scatter_pallas
from repro.kernels.head_gather import head_gather_matmul_pallas
from repro.kernels.pushsum_mix import pushsum_mix_pallas
from repro.kernels.topk_gather import topk_gather_pallas
from repro.optim import SGD, SGDState

M, K, D = 4096, 9, 4096          # the resident round's cell (S1)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _structs(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_gather_compiles(one_chip, dtype):
    args = _structs(one_chip, ((M, K), jnp.int32), ((M, K), jnp.float32),
                    ((M, D), dtype))
    c = _compile(gossip_gather_pallas, *args)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("accumulate", [False, True])
def test_gossip_scatter_compiles(one_chip, accumulate):
    args = _structs(one_chip, ((1024,), jnp.int32), ((1024, D), jnp.float32),
                    ((M, D), jnp.float32))
    c = _compile(lambda r, x, u: gossip_scatter_pallas(
        r, x, u, accumulate=accumulate), *args)
    assert "tpu_custom_call" in c.as_text()
    # U is aliased and aligned: the write-back copies no dormant row
    assert c.memory_analysis().temp_size_in_bytes < M * D * 4


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("accumulate", [False, True])
def test_gossip_scatter_misaligned_writes_in_place(one_chip, dtype,
                                                   accumulate):
    """Neither m nor d is a whole number of tiles: the kernel writes the
    whole tiles and XLA the partial edge tiles, both into the donated U —
    no copy of U is made."""
    m = d = M + 4
    args = _structs(one_chip, ((1024,), jnp.int32), ((1024, d), jnp.float32),
                    ((m, d), dtype))
    c = jax.jit(lambda r, x, u: gossip_scatter_pallas(
        r, x, u, accumulate=accumulate), donate_argnums=2).lower(
            *args).compile()
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    u_bytes = m * d * jnp.dtype(dtype).itemsize
    assert mem.alias_size_in_bytes >= u_bytes          # U is the output
    assert mem.temp_size_in_bytes < u_bytes // 16      # and is not copied


def test_topk_gather_compiles(one_chip):
    kk = D // 16                 # topk at ratio 1/16
    args = _structs(one_chip, ((M, K), jnp.int32), ((M, K), jnp.float32),
                    ((M, kk), jnp.float32), ((M, kk), jnp.int32))
    c = _compile(lambda i, w, v, cols: topk_gather_pallas(i, w, v, cols, D),
                 *args)
    assert "tpu_custom_call" in c.as_text()


def test_head_gather_compiles(one_chip):
    B, m, d, n = 64, 256, 64, 10   # serving cell: the CNN's head widths
    args = _structs(one_chip, ((B,), jnp.int32), ((B, d), jnp.float32),
                    ((m, d, n), jnp.float32), ((m, n), jnp.float32))
    c = _compile(head_gather_matmul_pallas, *args)
    assert "tpu_custom_call" in c.as_text()


def test_pushsum_mix_compiles(one_chip):
    args = _structs(one_chip, ((1024, 1024), jnp.float32),
                    ((1024, D), jnp.float32))
    c = _compile(pushsum_mix_pallas, *args)
    assert "tpu_custom_call" in c.as_text()


def _full_width_pads(hlo_text, width):
    """Per computation of the compiled text, its `pad`s whose result is
    `width` wide in the last dimension."""
    pads, comp = Counter(), None
    for line in hlo_text.splitlines():
        if line[:1].strip() and line.rstrip().endswith("{"):
            comp = line.split()[0]
        m = re.search(r"=\s*\w+\[([\d,]*)\]\S*\s+pad\(", line)
        if m and m.group(1).split(",")[-1] == str(width):
            pads[comp] += 1
    return pads


def test_u_step_row_gradient_has_no_pad_chain(one_chip):
    """The u-step over one client's flat row (twelve shared leaves, d_flat
    about 2^22): the row gradient is the leaf gradients concatenated, not
    one full-row pad per leaf summed in one fusion — a VPU-bound pass
    that took 96 ms a round at qwen2-0.5b's width."""
    cols = (4099, 1021, 3001, 2503, 2053, 3583, 1999, 4093, 2749, 1531,
            3067, 2837)
    h = 128
    params = {f"w{i:02d}": jax.ShapeDtypeStruct((1, h, c), jnp.float32)
              for i, c in enumerate(cols)}
    params["head"] = jax.ShapeDtypeStruct((1, h), jnp.float32)
    mask = {k: k != "head" for k in params}
    layout = gossip.FlatLayout.build(params, mask)
    assert len(layout.sizes) == 12 and 2 ** 21 < layout.d_flat < 2 ** 23

    def loss_fn(p, x):
        x = x * p["head"].astype(jnp.bfloat16)
        return sum(jnp.mean(jnp.square(
            (x @ p[f"w{i:02d}"].astype(jnp.bfloat16)).astype(jnp.float32)))
            for i in range(len(cols)))

    opt = SGD(lr=0.02, momentum=0.9, weight_decay=5e-4)

    def u_step(row, mom, head, x, mu):
        personal = {k: head if k == "head" else None for k in params}
        flat_loss = local.flat_view_loss(loss_fn, layout, personal)
        g = jax.grad(flat_loss)((row / mu).astype(row.dtype), x)
        row2, s2 = opt.update(g, SGDState(mom), row, 1.0)
        return row2, s2.momentum

    d = layout.d_flat
    args = _structs(one_chip, ((1, d), jnp.float32), ((1, d), jnp.float32),
                    ((1, h), jnp.float32), ((1, 16, h), jnp.bfloat16),
                    ((1,), jnp.float32))
    pads = _full_width_pads(_compile(jax.vmap(u_step), *args).as_text(), d)
    assert max(pads.values(), default=0) <= 1, pads
