"""Compile the device programs of the planning path for a described TPU
v5e at fleet widths (16,384 hosts: 16,448 ledger rows), without a chip.

These catch what interpret mode and the CPU backend cannot: a program the
TPU compiler refuses, or a kernel that does not fit its fast memory.
Nothing runs, so they say nothing about results or times.  The topology
is described inside a fixture, never at import, so that every test worker
collects the same tests and only the worker given this file loads the
TPU compiler.
"""
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from repro.kernels import ts_plan_device as dev  # noqa: E402

ROWS = 16_448   # ledger rows of tpu_dcn_fabric(n_pods=64, hosts_per_pod=256)
NP = 1024       # candidate bucket of a 1,024-task submit
WL = 4          # longest DCN path, in links
WB = 4096       # mirror width bucket


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's compiles cannot be read back from the persistent
    # cache: keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    with jax.enable_x64(True):
        return fn.lower(*specs).compile()


def _u64(s, *shape):
    return _spec(s, shape, jnp.uint64)


def _i64(s, *shape):
    return _spec(s, shape, jnp.int64)


@pytest.mark.parametrize("w", [64, 4096])
def test_wave_mirror_compiles(one_chip, w):
    s = one_chip
    fn = dev._build_wave_mirror(NP, WL, w, WB, int(dev._bits(0.1)))
    _compile(
        fn, _u64(s, ROWS, WB), _i64(s, NP, WL), _i64(s, NP), _u64(s, NP),
        _u64(s, NP), _u64(s, NP),
    )


def test_col_scan_compiles(one_chip):
    s = one_chip
    m = 256
    _compile(
        dev._build_col(NP, WL, m, WB), _u64(s, ROWS, WB), _i64(s, NP, WL),
        _i64(s, NP, m), _u64(s, NP), _u64(s, NP, m), _u64(s, NP),
    )


def test_select_compiles(one_chip):
    s = one_chip
    nc, ns = 4096, 1024
    _compile(
        dev._build_select(nc, ns), _u64(s, nc), _i64(s, nc), _i64(s, nc)
    )


def test_mirror_scatter_and_reindex_compile(one_chip):
    s = one_chip
    k = 4096
    _compile(
        dev._build_scatter(WB, k), _u64(s, ROWS, WB), _i64(s, k),
        _i64(s, k), _u64(s, k),
    )
    _compile(dev._build_reindex(WB, WB), _u64(s, ROWS, WB), _i64(s))


@pytest.mark.parametrize("w", [256, 4096])
def test_f32_pallas_kernel_compiles(one_chip, w):
    s = one_chip
    fn = dev._build_pallas(NP, 8, w, w, None, False)
    compiled = fn.lower(  # a float32 kernel: compiled without x64
        _spec(s, (NP, 8, w), jnp.float32), _spec(s, (NP, 1), jnp.float32),
        _spec(s, (NP, w), jnp.float32), _spec(s, (NP, 1), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
