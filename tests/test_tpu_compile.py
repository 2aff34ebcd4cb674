"""Compile the device path for a TPU v5e that is described, not attached.

The TPU compiler refuses what the Pallas interpreter and the CPU backend
accept: misaligned blocks, too much VMEM, programs that do not fit the
chip. These tests compile the serving kernels at qwen3-1.7b KV widths
and the Monte-Carlo drive loops and the working-set solver at the
paper's sizes, so a change that would fail on the chip fails here.
Nothing runs, so nothing here is a result or a time.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fastsim_jax, workingset
from repro.kernels import ops

# qwen3-1.7b KV geometry: 16 query heads over 8 KV heads of width 128.
H, KV, D = 16, 8, 128
# Shared-prefix decode: P prefixes of S tokens, M requests per prefix,
# each with its own suffix of Ss tokens, paged in blocks of 16 tokens.
P, M, S, SS, PAGE = 4, 8, 4096, 256, 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *args, **static):
    text = fn.lower(*args, **static).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    return text


def test_paged_attention_compiles(one_chip):
    pages_per_seq = (S + SS) // PAGE
    n_pages = P * S // PAGE + P * M * SS // PAGE
    bf = jnp.bfloat16
    _kernel_hlo(
        ops.paged_attention,
        _spec(one_chip, (P * M, H, D), bf),
        _spec(one_chip, (KV, n_pages, PAGE, D), bf),
        _spec(one_chip, (KV, n_pages, PAGE, D), bf),
        _spec(one_chip, (P * M, pages_per_seq), jnp.int32),
        _spec(one_chip, (P * M,), jnp.int32),
        interpret=False,
    )


def test_shared_prefix_attention_compiles(one_chip):
    bf = jnp.bfloat16
    _kernel_hlo(
        ops.shared_prefix_attention,
        _spec(one_chip, (P, M, H, D), bf),
        _spec(one_chip, (P, S, KV, D), bf),
        _spec(one_chip, (P, S, KV, D), bf),
        _spec(one_chip, (P,), jnp.int32),
        interpret=False,
    )


def test_shared_prefix_decode_compiles(one_chip):
    bf = jnp.bfloat16
    _kernel_hlo(
        ops.shared_prefix_decode,
        _spec(one_chip, (P, M, H, D), bf),
        _spec(one_chip, (P, S, KV, D), bf),
        _spec(one_chip, (P, S, KV, D), bf),
        _spec(one_chip, (P,), jnp.int32),
        _spec(one_chip, (P, M, SS, KV, D), bf),
        _spec(one_chip, (P, M, SS, KV, D), bf),
        _spec(one_chip, (P, M), jnp.int32),
        interpret=False,
    )


def test_flash_attention_compiles(one_chip):
    bf = jnp.bfloat16
    T = 2048
    _kernel_hlo(
        ops.flash_attention,
        _spec(one_chip, (1, T, H, D), bf),
        _spec(one_chip, (1, T, KV, D), bf),
        _spec(one_chip, (1, T, KV, D), bf),
        interpret=False,
    )


def _drive_args(sharding, J, N, n, R=None):
    """Argument shapes of one chunk of the drive loop: carried state,
    the chunk's (proxy, object) columns, then the runner's constants."""
    i32 = jnp.int32
    state = jax.eval_shape(lambda: fastsim_jax._init_state(J, N, batch=R))
    state = jax.tree_util.tree_map(
        lambda s: _spec(sharding, s.shape, s.dtype), state
    )
    lane = () if R is None else (R,)
    scalar = _spec(sharding, (), i32)
    return (
        state,
        _spec(sharding, lane + (n,), i32),
        _spec(sharding, lane + (n,), i32),
        scalar,
        _spec(sharding, (N,), i32),
        _spec(sharding, (J,), i32),
        _spec(sharding, (J,), i32),
        _spec(sharding, (J + 2,), i32),
        scalar,
        scalar,
        scalar,
    )


def test_single_lane_drive_compiles_at_fig2_size(one_chip):
    # Fig. 2: J=9 proxies over N=1e6 objects, fed in 250k-request chunks
    N = 1_000_000
    fn = fastsim_jax._single_fn(True, N)
    compiled = fn.lower(*_drive_args(one_chip, 9, N, 250_000)).compile()
    assert compiled.memory_analysis() is not None


def test_batched_drive_compiles_at_table1_size(one_chip):
    # Table I: J=3 proxies over N=1000 objects, R=8 lanes
    N = 1000
    fn = fastsim_jax._batched_fn(True, N)
    fn.lower(*_drive_args(one_chip, 3, N, 100_000, R=8)).compile()


def test_batched_solver_compiles_at_table2_size(one_chip):
    # Table II: the 8 allocation combinations of a J=3, N=1000 system
    J, N, K = 3, 1000, 8
    fn = workingset._jitted_solver("L1", 8, 200, 90, 0.7, 1e-7, True)
    fn.lower(
        _spec(one_chip, (J, N), jnp.float32),
        _spec(one_chip, (N,), jnp.float32),
        _spec(one_chip, (K, J), jnp.float32),
    ).compile()

