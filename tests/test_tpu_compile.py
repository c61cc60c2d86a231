"""The ELL kernels compile for a TPU v5e at Graph500 scale-22 widths.

Compiled with ``interpret=False`` for a described (not attached) v5e,
so Mosaic's refusals — unaligned blocks, in-kernel int indexing, VMEM
over-use — show up here at no chip time.  Nothing runs; results are
checked by the interpret-mode tests in test_kernels.py.

The topology is described inside a module fixture: only the worker
that runs this file loads the TPU compiler, and every worker collects
the same tests.
"""
import os
import signal

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import csr_relax as K
from repro.kernels import pallas_repair as FK
from repro.kernels.ell import ell_capacity

N = 2 ** 22                        # RMAT scale 22
E_CAP = 16 * N + 2 * (16 * N // 2000)   # generated edges + auto diff pool
LANES = 8
R = ell_capacity(N, E_CAP, LANES)
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # Loading the TPU compiler installs a failure handler that dumps a
    # stack trace on SIGTERM; keep the worker's own disposition so a run
    # stopped by its time limit ends quietly, with its output intact.
    on_term = signal.getsignal(signal.SIGTERM)
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:         # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        signal.signal(signal.SIGTERM, on_term)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return {"src": s((R, LANES), jnp.int32), "w": s((R, LANES), jnp.int32),
            "vi": s((N + 1,), jnp.int32), "vf": s((N + 1,), jnp.float32),
            "row": s((R,), jnp.int32)}


KERNELS = {
    "fused_relax_b128": (lambda a, b, c: FK.fused_relax_rows(
        a, b, c, block=128, interpret=False), ("src", "w", "vi")),
    "fused_relax_b512": (lambda a, b, c: FK.fused_relax_rows(
        a, b, c, block=512, interpret=False), ("src", "w", "vi")),
    "fused_spmv_b128": (lambda a, b, c: FK.fused_spmv_rows(
        a, b, c, block=128, interpret=False), ("src", "row", "vf")),
    "fused_spmv_b512": (lambda a, b, c: FK.fused_spmv_rows(
        a, b, c, block=512, interpret=False), ("src", "row", "vf")),
    "relax_rowmin": (lambda a, b, c: K.relax_rowmin(
        a, b, c, interpret=False), ("src", "w", "vi")),
    "spmv_rowsum": (lambda a, c: K.spmv_rowsum(
        a, c, interpret=False), ("src", "vf")),
    "relax_rowargmin": (lambda a, b, c, d: K.relax_rowargmin(
        a, b, c, d, n=N, interpret=False), ("src", "w", "vi", "row")),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    """Each launch (with its XLA-side gather) compiles with the Mosaic
    kernel in it and fits the chip's 16 GB."""
    fn, arg_names = KERNELS[name]
    shapes = _shapes(one_chip)
    compiled = jax.jit(fn).lower(*(shapes[a] for a in arg_names)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES
