"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one program of the served path
at the Wikipedia deployment's shape for ``SingleDeviceSharding`` on a
``v5e:2x2`` topology that is described, not attached. The TPU compiler then
refuses what a chip would refuse (tiling, VMEM, device memory) before any
chip time is spent. The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.matroid import MatroidSpec

# the Wikipedia deployment (chip_smoke.py): d=25, transversal over 100
# topics with up to 3 per page, rank 100
D, H, GAMMA, K, TAU = 25, 100, 3, 100, 32
WIKI = MatroidSpec("transversal", num_categories=H, gamma=GAMMA)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def spec_of(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def compile_text(fn, *args, **kw) -> str:
    return fn.lower(*args, **kw).compile().as_text()


def test_pdist_kernel_compiles(one_chip):
    from repro.kernels.pdist import pairwise_sqdist

    x = spec_of(one_chip, (4096, D))
    assert "tpu_custom_call" in compile_text(pairwise_sqdist, x, x)


def test_precheck_kernel_compiles(one_chip):
    from repro.kernels.precheck import center_precheck_stats

    hlo = compile_text(
        center_precheck_stats,
        spec_of(one_chip, (512, D)),
        spec_of(one_chip, (TAU * 2 + 1, D)),
        spec_of(one_chip, (TAU * 2 + 1,), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


def test_blocked_ingest_with_pallas_precheck_compiles(one_chip, monkeypatch):
    from repro.core.streaming import ingest_batch_donated, init_stream_state
    from repro.kernels import ops

    # steer the precheck to the Pallas kernel: the backend here is the CPU
    monkeypatch.setattr(ops, "_FORCE", "pallas")
    st = jax.tree.map(
        lambda s: spec_of(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: init_stream_state(D, GAMMA, WIKI, K, TAU)),
    )
    n = 4096
    hlo = compile_text(
        ingest_batch_donated,
        st,
        spec_of(one_chip, (n, D)),
        spec_of(one_chip, (n, GAMMA), jnp.int32),
        spec_of(one_chip, (n,), jnp.bool_),
        WIKI, None, K, TAU,
        base_index=spec_of(one_chip, (), jnp.int32),
        block_size=128,
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kind", ["partition", "transversal"])
def test_sum_batch_solver_compiles(one_chip, kind):
    from repro.core.solvers.jit_sum import (
        solve_sum_batch, solve_sum_batch_transversal,
    )

    m, B, kmax = 4096, 8, 128
    common = dict(
        allow=spec_of(one_chip, (B, m), jnp.bool_),
        ks=spec_of(one_chip, (B,), jnp.int32),
        gammas=spec_of(one_chip, (B,)),
        kmax=kmax,
    )
    Dm = spec_of(one_chip, (m, m))
    if kind == "partition":
        compiled = solve_sum_batch.lower(
            Dm, spec_of(one_chip, (m,), jnp.int32),
            spec_of(one_chip, (B, 16), jnp.int32), **common,
        ).compile()
    else:
        compiled = solve_sum_batch_transversal.lower(
            Dm, spec_of(one_chip, (m, H), jnp.bool_), **common,
        ).compile()
    assert compiled.memory_analysis() is not None
