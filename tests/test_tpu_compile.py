"""The main path's kernels compile for a TPU v5e at the published widths.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests run on the CPU.  They catch what
interpret mode cannot: a kernel Mosaic refuses (unsupported layouts, more
VMEM than a kernel may use) and a program that does not fit the chip's
16 GB of HBM.  Shapes are SecStr's (N = 83,679, d = 315) unless a test
says otherwise; nothing runs, so the tests say nothing about results or
speed.

The topology is described inside a module fixture, never while the module
is imported: only one process may load the TPU library, and the test
workers must all collect the same tests.  The persistent compile cache is
off around these compiles — an entry compiled for a described chip cannot
be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, D = 83679, 315          # SecStr, the paper's Table 1
HBM_BYTES = 16 * 10**9     # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"needs {used / 1e9:.2f} GB of HBM"
    return compiled


def _exact_scan(divergence=None):
    from repro.kernels.fused_lp.batched import fused_lp_scan_folded_resume_kernel

    return lambda x, y, y0, a, n_it: fused_lp_scan_folded_resume_kernel(
        x, y, y0, 0.35, a, n_it, divergence=divergence)


@pytest.mark.parametrize("d,k,divergence", [
    (D, 3, None),
    (D, 1024, None),
    (D, 4096, None),      # max_batch 32 x width bucket 128
    (500, 4096, None),    # Pascal alpha's width
    (D, 4096, "kl"),
])
def test_exact_resume_scan_compiles(one_chip, d, k, divergence):
    """The exact backend's folded scan, at every width the engine forms."""
    f32 = jnp.float32
    compiled = _compile(_exact_scan(divergence), ((N, d), f32), ((N, k), f32),
                        ((N, k), f32), ((k,), f32), ((), jnp.int32),
                        sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_exact_row_base_variant_compiles(one_chip):
    """The sharded engine's per-device stripe: a quarter of the padded
    rows against all columns, global row offset as a traced operand."""
    from repro.kernels.fused_lp.batched import _alpha_row, _folded_call

    sp, k = 83712, 256                    # N padded to the 256-row tile
    rows = -(-(sp // 4) // 256) * 256     # one of four stripes, re-tiled

    def step(x_rows, x_full, y_full, y0_rows, alpha, row_base):
        return _folded_call(x_rows, x_full, y_full, y0_rows,
                            _alpha_row(alpha, k), inv_two_sigma_sq=4.0,
                            n_valid=N, block_m=256, block_n=256,
                            interpret=False, row_base=row_base)

    f32 = jnp.float32
    compiled = _compile(step, ((rows, D), f32), ((sp, D), f32), ((sp, k), f32),
                        ((rows, k), f32), ((k,), f32), ((), jnp.int32),
                        sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_grf_walker_path_compiles(one_chip):
    """The grf backend's streamed walk at m = 100 walkers, over the dense
    4096-node kernel graph the engine's grf_graph() builds."""
    from repro.core.grf import _lp_streamed

    n, m, k = 4096, 100, 2
    fn = functools.partial(_lp_streamed, n_iters=10, n_walkers=m, p_halt=0.0)
    _compile(fn, ((n, n - 1), jnp.int32), ((n, n - 1), jnp.float32),
             ((n,), jnp.int32), ((n, k), jnp.float32), ((k,), jnp.float32),
             ((2,), jnp.uint32), sharding=one_chip)


def test_pairwise_kernel_compiles(one_chip):
    from repro.kernels.pairwise.pairwise import pairwise_sq_dists_kernel

    compiled = _compile(
        functools.partial(pairwise_sq_dists_kernel, interpret=False),
        ((4096, D), jnp.float32), ((N, D), jnp.float32), sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [2, 64, 4096])  # one request to a full slab walk
def test_vdt_scan_compiles_without_a_sort(one_chip, k):
    """The VDT walk over SecStr's scan table (4N blocks, 2^17 leaves): the
    segment-sum takes its row nodes as sorted, so no sort runs per step."""
    from repro.core.label_prop import lp_scan_leaforder
    from repro.core.matvec import scan_slots

    L = 17
    slots = scan_slots(4 * N)
    fn = functools.partial(lp_scan_leaforder.__wrapped__, L=L, n_iters=50)
    f32, i32 = jnp.float32, jnp.int32
    compiled = _compile(fn, ((1 << L, k), f32), ((1 << L, 1), f32),
                        ((slots,), i32), ((slots,), i32), ((slots,), f32),
                        ((k,), f32), sharding=one_chip)
    assert " sort(" not in compiled.as_text()
