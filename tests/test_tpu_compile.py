"""The main path's kernels compile for a TPU v5e, at real sizes, here.

The TPU compiler is installed with ``libtpu``: it compiles for a chip
that is described (``v5e:2x2``) rather than attached.  Each test lowers
a kernel with abstract operands placed on the described devices,
compiles it, and asserts the compiled program holds the Pallas kernel
(``tpu_custom_call``) — so a kernel the chip's compiler refuses fails
here and not on the chip.  The fused kernel compiles with its VMEM limit
set to the layout's window estimate (``EdgeBlockLayout.window_bytes``),
so an estimate below what the compiler needs fails here too.  One more
compiles the certificate's pseudo-inverse at chip scale, the program
that stopped the first run on the chip.

The topology is described inside a module fixture, never while the file
is imported (only one process may hold the TPU library, and pytest's
workers all import every test file); the persistent compilation cache is
off around every compile, since an entry written for a described chip
cannot be read back without one.
"""
import importlib.util
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api.losses import LassoLoss, LogisticLoss, SquaredLoss
from repro.api.regularizers import TotalVariation
from repro.core.graph import (fused_vmem_cap, fused_window_bytes,
                              grid_graph, plan_edge_blocks)
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    if importlib.util.find_spec("libtpu") is None:
        # the one case with nothing to check: no TPU compiler installed.
        # Any other failure to describe the chip fails every test here.
        warnings.warn("libtpu is not installed: no TPU compile is checked")
        pytest.skip("libtpu is not installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _prox_leaves(loss, n=2, m=5):
    """Sorted keys and per-node shapes of ``loss.prox_setup``'s leaves
    for ``m`` samples of ``n`` features per node (the paper's §5
    widths)."""
    from repro.core.losses import NodeData
    f32 = jnp.float32
    data = NodeData(jax.ShapeDtypeStruct((1, m, n), f32),
                    jax.ShapeDtypeStruct((1, m), f32),
                    jax.ShapeDtypeStruct((1, m), f32),
                    jax.ShapeDtypeStruct((1,), f32))
    params = jax.eval_shape(loss.prox_setup, data,
                            jax.ShapeDtypeStruct((1,), f32))
    keys = tuple(sorted(params))
    return keys, tuple(params[k].shape[1:] for k in keys)


def _fused_operands(lt, sharding, shapes, n=2):
    """Abstract fused-kernel operands for layout ``lt``; ``shapes`` are
    the per-node prox parameter shapes (sorted keys)."""
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    wr = (lt.num_blocks + lt.kn - 1) * lt.block_nodes
    er = (lt.num_blocks + lt.klo + lt.khi) * lt.block_edges
    ne = lt.num_blocks * lt.block_edges
    return (s((wr, n)), s((er, n)), s((er, 2), jnp.int32),
            tuple(s((wr,) + sh) for sh in shapes), s((wr, 1)), s((ne, 1)),
            s((ne, 1)))


def _limit_vmem(monkeypatch, nbytes: int) -> None:
    """Let the fused kernel claim only ``nbytes`` of VMEM (its window
    estimate) instead of the whole cap."""
    from repro.kernels import pd_step
    monkeypatch.setattr(pd_step, "fused_vmem_cap", lambda: nbytes)
    jax.clear_caches()          # no trace made under another limit


def _compile_fused(lt, sharding, loss=None, **kw):
    from repro.kernels.pd_step import fused_pd_step
    loss = SquaredLoss() if loss is None else loss
    pkeys, shapes = _prox_leaves(loss)
    kw = dict(loss=loss, reg=TotalVariation(), pkeys=pkeys,
              block_nodes=lt.block_nodes, block_edges=lt.block_edges,
              kn=lt.kn, klo=lt.klo, khi=lt.khi, rho=1.9, interpret=False,
              **kw)
    return jax.jit(lambda *a: fused_pd_step(*a, **kw)).lower(
        *_fused_operands(lt, sharding, shapes)).compile()


@pytest.mark.parametrize("loss", [SquaredLoss(), LassoLoss(),
                                  LogisticLoss()],
                         ids=lambda loss: type(loss).__name__)
def test_fused_kernel_compiles_at_lattice_layout(loss, one_chip,
                                                 no_compile_cache,
                                                 monkeypatch):
    """512x512 lattice (grid2d's family): the multi-block banded layout
    the auto-tuner picks for the loss under the VMEM cap, with the
    in-kernel eq.-11 residual.  Every kernel-safe loss: their prox
    parameters have their own shapes, and the logistic prox runs a
    Newton loop in-kernel."""
    assert loss.kernel_safe
    pf = loss.prox_param_floats(5, 2)
    g = grid_graph(np.random.default_rng(0), 512, 512)
    lt = plan_edge_blocks(g, window_hint=(2, pf, 4, fused_vmem_cap()))
    assert lt.num_blocks > 1 and lt.kn <= 3
    window = lt.window_bytes(2, param_floats=pf)
    assert window <= fused_vmem_cap()
    _limit_vmem(monkeypatch, window)
    compiled = _compile_fused(lt, one_chip, loss, compute_residual=True)
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_kernel_compiles_on_single_block_with_iterations(
        one_chip, no_compile_cache, monkeypatch):
    """The paper's §5 setup is one whole-graph block: the kernel runs
    ``iters`` iterations in VMEM and accumulates the residual."""
    from repro.data.synthetic import make_sbm_regression
    g = make_sbm_regression(seed=0).graph
    lt = plan_edge_blocks(g)
    assert lt.num_blocks == 1 and lt.kn == 1
    _limit_vmem(monkeypatch, lt.window_bytes(2))
    compiled = _compile_fused(lt, one_chip, iters=10,
                              compute_residual=True)
    assert "tpu_custom_call" in compiled.as_text()


def test_unfused_kernels_compile_at_million_node_scale(one_chip,
                                                       no_compile_cache):
    """The unfused pallas route at 1M nodes / 10.2M edges (n = 2): the
    dual clip over every edge and the ridge prox over every node."""
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def step(u, bound, p, v):
        return ops.tv_prox(u, bound, interpret=False), \
            ops.batched_affine(p, v, interpret=False)

    compiled = jax.jit(step).lower(
        s((10_200_000, 2)), s((10_200_000,)), s((1_000_000, 2, 2)),
        s((1_000_000, 2))).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_kernels_carry_stable_names(one_chip, no_compile_cache):
    """Each Pallas kernel lowers under its own name, which a device
    trace's op and a benchmark's reduction key on: ``fused_pd_step``,
    ``tv_prox`` and ``batched_affine``.  The fused kernel compiles to
    an instruction of that name."""
    import re

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def step(u, bound, p, v):
        return ops.tv_prox(u, bound, interpret=False), \
            ops.batched_affine(p, v, interpret=False)

    low = jax.jit(step).lower(s((1024, 2)), s((1024,)), s((512, 2, 2)),
                              s((512, 2)))
    names = re.findall(r'kernel_name = "([^"]*)"', low.as_text())
    assert sorted(names) == ["batched_affine", "tv_prox"]

    from repro.kernels.pd_step import fused_pd_step
    g = grid_graph(np.random.default_rng(0), 16, 16)
    lt = plan_edge_blocks(g)
    pkeys, shapes = _prox_leaves(SquaredLoss())
    low = jax.jit(lambda *a: fused_pd_step(
        *a, loss=SquaredLoss(), reg=TotalVariation(), pkeys=pkeys,
        block_nodes=lt.block_nodes, block_edges=lt.block_edges, kn=lt.kn,
        klo=lt.klo, khi=lt.khi, rho=1.9, compute_residual=True,
        interpret=False)).lower(*_fused_operands(lt, one_chip, shapes))
    assert re.findall(r'kernel_name = "([^"]*)"',
                      low.as_text()) == ["fused_pd_step"]
    assert "%fused_pd_step" in low.compile().as_text()


def test_certificate_pseudo_inverse_compiles_at_million_nodes(
        one_chip, no_compile_cache):
    """The eq.-11 certificate's per-node pseudo-inverse at 1M nodes
    (``jnp.linalg.pinv``'s batched SVD overflows the TPU compiler's
    scoped VMEM there, after minutes of compiling)."""
    from repro.engine.step import psd_pinv_solve

    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    jax.jit(psd_pinv_solve).lower(s((1_000_000, 2, 2)),
                                  s((1_000_000, 2))).compile()


def test_sharded_fused_shard_body_compiles_on_four_chips(
        topo, no_compile_cache, monkeypatch):
    """The ``sharded_fused`` shard_map program — dual halo refresh plus
    the fused kernel in every shard — over a mesh of the four described
    chips."""
    from repro.core.distributed import _make_hier_run, shard_problem_fused
    from repro.scenarios.zoo import lattice_dataset

    # this process's backend is the CPU, whose default is the jnp
    # reference: steer the shard body to the kernel the chip runs
    monkeypatch.setattr(ops, "_use_kernel_default", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    ds = lattice_dataset(np.random.default_rng(0), 128)
    sp = shard_problem_fused(ds.graph, ds.data, 4, window_hint=(
        2, SquaredLoss().prox_param_floats(5, 2), 4, fused_vmem_cap()))
    h = sp.hier
    _limit_vmem(monkeypatch, fused_window_bytes(
        h.block_nodes, h.block_edges, h.kn, h.klo, h.khi, 2))
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))
    run = _make_hier_run(sp, mesh, 1e-3, axis="data", rho=1.9,
                         comm="boundary", num_iters=5, with_residual=True)
    operands = ((h.num_shards * h.w_store_rows, 2),
                (h.num_shards * h.u_store_rows, 2)) + tuple(
        a.shape for a in (sp.tau, sp.node_owned, sp.ends, sp.bound_unit,
                          sp.edge_owned, sp.orient, sp.send_idx,
                          sp.send_flip, sp.recv_src_boundary,
                          sp.recv_flip))
    dtypes = (jnp.float32, jnp.float32) + tuple(
        a.dtype for a in (sp.tau, sp.node_owned, sp.ends, sp.bound_unit,
                          sp.edge_owned, sp.orient, sp.send_idx,
                          sp.send_flip, sp.recv_src_boundary,
                          sp.recv_flip))
    leaves = [sp.prox_params[k] for k in sorted(sp.prox_params)]
    args = [jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(
            mesh, P("data", *(None,) * (len(shape) - 1))))
        for shape, dt in zip(operands + tuple(a.shape for a in leaves),
                             dtypes + tuple(a.dtype for a in leaves))]
    compiled = jax.jit(run).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
